"""Mean time per encode slab that its reads queued on the shared ec_read
pool before the first of them started: delta of
SeaweedFS_ec_pipeline_read_queue_seconds{op=encode} over delta of
SeaweedFS_ec_pipeline_slabs{op=encode} in the window, in ms, every
pipeline together (program counters). Part of pipeline_read_ms.encode.
None where the program has no such counter."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="encode")
    if slabs <= 0 or not any(
            name == "SeaweedFS_ec_pipeline_read_queue_seconds"
            for name, _ in run.counters_after):
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_read_queue_seconds",
                           op="encode") / slabs
