"""The 90th percentile of latency over every GET due in the window, each
timed from its due time to its last body byte; a failed, wrong or
unanswered GET counts as slower than every other. Nearest rank (host
clock). With shards lost, about a fifth of GETs reconstruct on the
device, so this rank lies among them."""

import math


def read(run):
    lat = sorted(run.host.get("latency_ms") or [])
    if not lat:
        return None
    return lat[max(0, math.ceil(0.90 * len(lat)) - 1)]
