"""Mean time an encode flush waited for the dispatch scheduler's lock
before its pack and coder call, per encode batch of the window: delta of
SeaweedFS_ec_dispatch_lock_wait_seconds{lane=encode} over delta of
SeaweedFS_ec_dispatch_batches{lane=encode}, in ms, all chips together
(program counters). Every chip's lane flushes under that one lock, from
the flusher thread or a demand-flushing pipeline. None where the program
has no such counter."""


def read(run):
    batches = run.delta("SeaweedFS_ec_dispatch_batches", lane="encode")
    if batches <= 0 or not any(
            name == "SeaweedFS_ec_dispatch_lock_wait_seconds"
            for name, _ in run.counters_after):
        return None
    return 1e3 * run.delta("SeaweedFS_ec_dispatch_lock_wait_seconds",
                           lane="encode") / batches
