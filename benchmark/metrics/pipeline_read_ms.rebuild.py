"""Mean time per slab the EC file pipeline's rebuild spent reading its
survivor rows from the shard files (reader thread): delta of
SeaweedFS_ec_pipeline_seconds{op=rebuild, phase=read} over delta of
SeaweedFS_ec_pipeline_slabs{op=rebuild} in the window, in ms (program
counters)."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="rebuild")
    if slabs <= 0:
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_seconds", op="rebuild",
                           phase="read") / slabs
