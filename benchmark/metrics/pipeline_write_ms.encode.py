"""Mean time per slab the EC file pipeline's encode spent writing its 14
shard rows (shard-writer threads, summed over threads: writer thread-ms
per slab): delta of SeaweedFS_ec_pipeline_seconds{op=encode,
phase=write} over delta of SeaweedFS_ec_pipeline_slabs{op=encode} in the
window, in ms (program counters)."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="encode")
    if slabs <= 0:
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_seconds", op="encode",
                           phase="write") / slabs
