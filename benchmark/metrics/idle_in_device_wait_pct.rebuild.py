"""Of the device-idle time while a rebuild call was in flight, the share in
which the program's `ec.rebuild.device_wait` span was open: the
coordinator blocked on the device. Device ops, `bench:rebuild` calls and
program spans all come from the one profiler trace of the run; mean over
the cell's chips."""

import progtrace


def read(run):
    spans = progtrace.of_run(run).get("ec.rebuild.device_wait")
    if not spans:
        return None
    return progtrace.idle_share_under(run.timeline, "rebuild", spans,
                                      list(range(run.chips)))
