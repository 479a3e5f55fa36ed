"""Mean time per slab the EC file pipeline's rebuild spent blocked on the
device until the slab's rebuilt rows were host bytes (coordinator
thread: batch window, transfers and kernel): delta of
SeaweedFS_ec_pipeline_seconds{op=rebuild, phase=device_wait} over delta
of SeaweedFS_ec_pipeline_slabs{op=rebuild} in the window, in ms (program
counters)."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="rebuild")
    if slabs <= 0:
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_seconds", op="rebuild",
                           phase="device_wait") / slabs
