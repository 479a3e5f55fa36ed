"""The traced run's device timeline, reduced from the JAX profiler's trace.

`Timeline.from_xspace` reads the `.xplane.pb` the profiler writes. Device
planes are those named `/device:TPU:<n>`; each device's busy intervals are
the union of the events on its op line ("XLA Ops"). Host spans come from
the benchmark's own `jax.profiler.TraceAnnotation`s, whose names start with
`bench:`. Both are in seconds from the start of the trace; on a v5e the
device events were seen to lead the host annotations by about 1.4 ms
(tests/data/v5e_small.xplane.pb), small against the seconds-long calls
whose in-flight time the metrics divide by.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

PREFIX = "bench:"
_OP_LINES = ("XLA Ops",)
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """An op event's HLO text cut to its name and result shape."""
    return _LAYOUT.sub("", hlo).split("(", 1)[0].strip()[:120]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(xs: list[tuple[float, float]],
            ys: list[tuple[float, float]]) -> float:
    """Length of the intersection of two interval unions."""
    xs, ys = union(xs), union(ys)
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def length(xs: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in union(xs))


@dataclass
class Timeline:
    # device id -> [(start, end, op name)]
    ops: dict[int, list[tuple[float, float, str]]] = field(default_factory=dict)
    # annotation name (without the prefix) -> [(start, end)]
    host: dict[str, list[tuple[float, float]]] = field(default_factory=dict)

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Timeline":
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        from jax.profiler import ProfileData

        return cls.from_xspace(ProfileData.from_file(paths[-1]))

    @classmethod
    def from_xspace(cls, space) -> "Timeline":
        tl = cls()
        for plane in space.planes:
            if plane.name.startswith("/device:TPU:") and \
                    plane.name[len("/device:TPU:"):].isdigit():
                dev = int(plane.name[len("/device:TPU:"):])
                evs = tl.ops.setdefault(dev, [])
                for line in plane.lines:
                    if line.name not in _OP_LINES:
                        continue
                    for e in line.events:
                        s = e.start_ns / 1e9
                        evs.append((s, s + e.duration_ns / 1e9, e.name))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            s = e.start_ns / 1e9
                            tl.host.setdefault(e.name[len(PREFIX):], []) \
                                .append((s, s + e.duration_ns / 1e9))
        return tl

    def busy(self, dev: int) -> list[tuple[float, float]]:
        return union([(a, b) for a, b, _ in self.ops.get(dev, [])])

    def spans(self, name: str) -> list[tuple[float, float]]:
        return self.host.get(name, [])

    def busy_in(self, name: str, devices: list[int]) -> list[float]:
        """Per device: seconds busy inside the union of `name` spans."""
        spans = self.spans(name)
        return [overlap(self.busy(d), spans) for d in devices]

    def idle_pct(self, name: str, devices: list[int]) -> float | None:
        """100 * (1 - busy / in-flight time) inside `name`, mean over
        devices; None where no such span or no device plane was traced."""
        devices = [d for d in devices if d in self.ops]
        inflight = length(self.spans(name))
        if inflight <= 0 or not devices:
            return None
        busy = self.busy_in(name, devices)
        return sum(100.0 * (1.0 - b / inflight) for b in busy) / len(busy)

    def top_ops(self, name: str, devices: list[int], n: int = 10):
        """[[op, seconds]] of the ops that took most device time inside
        `name`, summed over devices."""
        spans = union(self.spans(name))
        acc: dict[str, float] = {}
        for d in devices:
            for a, b, op in self.ops.get(d, []):
                t = overlap([(a, b)], spans)
                if t > 0:
                    key = op_name(op)
                    acc[key] = acc.get(key, 0.0) + t
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, name: str, devices: list[int], n: int = 10):
        """[[label, seconds]] of the longest device-idle gaps inside
        `name`, each labelled by the innermost benchmark span that covers
        its middle (what the host was doing)."""
        out = []
        for lo, hi in union(self.spans(name)):
            for d in devices:
                t = lo
                for a, b in self.busy(d) + [(hi, hi)]:
                    a, b = max(a, lo), min(b, hi)
                    if a > t:
                        out.append((a - t, d, (t + a) / 2))
                    t = max(t, b)
                    if t >= hi:
                        break
        out.sort(key=lambda g: -g[0])
        labelled = []
        for gap, d, mid in out[:n]:
            covering = [(b - a, k) for k, iv in self.host.items()
                        if k != name for a, b in iv if a <= mid <= b]
            what = min(covering)[1] if covering else "no call in flight"
            labelled.append([f"chip {d}: {what}", gap])
        return labelled
