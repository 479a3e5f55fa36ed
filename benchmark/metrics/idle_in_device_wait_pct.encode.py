"""Of the device-idle time while an encode call was in flight, the share in
which the program's `ec.encode.device_wait` span was open: the
coordinator blocked on the device. Device ops, `bench:encode` calls and
program spans all come from the one profiler trace of the run; mean over
the cell's chips."""

import progtrace


def read(run):
    spans = progtrace.of_run(run).get("ec.encode.device_wait")
    if not spans:
        return None
    return progtrace.idle_share_under(run.timeline, "encode", spans,
                                      list(range(run.chips)))
