"""Device idle share while the traced GET window was open: 100 * (1 - union of device-op
intervals / in-flight time), from the profiler trace, mean over the
cell's chips."""


def read(run):
    if run.timeline is None:
        return None
    return run.timeline.idle_pct("get", list(range(run.chips)))
