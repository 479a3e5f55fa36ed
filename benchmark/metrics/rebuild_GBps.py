"""Bytes of shard files rebuilt by the VolumeEcShardsRebuild calls of the
window, over the time in which at least one of them was in flight (client
clock). 1 GB = 10^9 B."""

import devtrace


def read(run):
    calls = run.host.get("rebuilds") or []
    if not calls:
        return None
    inflight = devtrace.length([(a, b) for a, b, _ in calls])
    return sum(n for _, _, n in calls) / inflight / 1e9
