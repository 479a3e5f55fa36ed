"""Mean time per slab the EC file pipeline's encode spent reading its k
data rows from the .dat (reader thread, zero fill included): delta of
SeaweedFS_ec_pipeline_seconds{op=encode, phase=read} over delta of
SeaweedFS_ec_pipeline_slabs{op=encode} in the window, in ms (program
counters)."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="encode")
    if slabs <= 0:
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_seconds", op="encode",
                           phase="read") / slabs
