"""Mean time per slab the EC file pipeline's encode spent blocked on the
device until the slab's parity was host bytes (coordinator thread: batch
window, transfers and kernel): delta of
SeaweedFS_ec_pipeline_seconds{op=encode, phase=device_wait} over delta
of SeaweedFS_ec_pipeline_slabs{op=encode} in the window, in ms (program
counters)."""


def read(run):
    slabs = run.delta("SeaweedFS_ec_pipeline_slabs", op="encode")
    if slabs <= 0:
        return None
    return 1e3 * run.delta("SeaweedFS_ec_pipeline_seconds", op="encode",
                           phase="device_wait") / slabs
