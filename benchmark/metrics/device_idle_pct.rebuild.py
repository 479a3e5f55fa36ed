"""Device idle share while a rebuild call was in flight: 100 * (1 - union of device-op
intervals / in-flight time), from the profiler trace, mean over the
cell's chips."""


def read(run):
    if run.timeline is None:
        return None
    return run.timeline.idle_pct("rebuild", list(range(run.chips)))
