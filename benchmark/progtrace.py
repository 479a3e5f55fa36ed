"""The program's own spans in a traced run's profiler trace.

While a JAX profiler capture runs, the program's spans (seaweedfs_tpu/
utils/trace.py) write themselves into it as host events of their own
names, on the thread that ran them. This reads them from the `.xplane.pb`
that `devtrace.Timeline` reads the device ops and the benchmark's `bench:`
spans from, on the same clock (seconds from the start of the trace), so a
device-idle gap can be put down to what the program was doing.

A program whose spans never reach the profiler's trace gives no spans
here, and every share below is then None.
"""

from __future__ import annotations

import glob
import os

import devtrace

TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "out", "trace")
# the per-slab and per-batch spans of the EC file pipeline and the
# dispatch scheduler (not the calls' root spans)
PIPELINE = ("ec.encode.", "ec.rebuild.", "ec.dispatch.")


def from_xspace(space, prefixes: tuple) -> dict[str, list]:
    """{span name: [(start, end)]} of the host events whose names start
    with one of `prefixes`."""
    out: dict[str, list] = {}
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    s = e.start_ns / 1e9
                    out.setdefault(e.name, []).append(
                        (s, s + e.duration_ns / 1e9))
    return out


def from_dir(trace_dir: str, prefixes: tuple) -> dict[str, list]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return {}
    from jax.profiler import ProfileData

    return from_xspace(ProfileData.from_file(paths[-1]), prefixes)


def of_run(run) -> dict[str, list]:
    """The EC pipeline's spans in a traced run's trace, read once per
    run; {} for an untraced run."""
    if run.timeline is None:
        return {}
    if "program_spans" not in run.state:
        run.state["program_spans"] = from_dir(TRACE_DIR, PIPELINE)
    return run.state["program_spans"]


def intersect(xs: list, ys: list) -> list:
    """The intersection of two interval unions, as intervals."""
    xs, ys = devtrace.union(xs), devtrace.union(ys)
    out = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_share_under(tl, call: str, spans: list,
                     devices: list) -> float | None:
    """Of the device-idle time while a `call` benchmark span was open,
    the share (%) in which at least one of `spans` was open too; mean
    over the devices traced. None where there is no such call, no such
    span, no device plane or no idle time."""
    devices = [d for d in devices if d in tl.ops]
    calls = devtrace.union(tl.spans(call))
    under = intersect(calls, spans)
    if not calls or not spans or not devices:
        return None
    shares = []
    for d in devices:
        busy = tl.busy(d)
        idle = devtrace.length(calls) - devtrace.overlap(busy, calls)
        if idle <= 0:
            return None
        idle_under = devtrace.length(under) - devtrace.overlap(busy, under)
        shares.append(100.0 * idle_under / idle)
    return sum(shares) / len(shares)
