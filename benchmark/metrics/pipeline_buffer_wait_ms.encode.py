"""Mean time per encode slab that the EC file pipeline's reader thread
waited for a recycled slab buffer: delta of
SeaweedFS_ec_pipeline_buffer_wait_seconds{op=encode} over delta of
SeaweedFS_ec_pipeline_slabs{op=encode} in the window, in ms, every
pipeline together (program counters). A buffer comes back once its
slab's data rows are on disk and its parity is host bytes. None where
the program has no such counter."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="encode")
    if slabs <= 0 or not any(
            name == "SeaweedFS_ec_pipeline_buffer_wait_seconds"
            for name, _ in run.counters_after):
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_buffer_wait_seconds",
                           op="encode") / slabs
