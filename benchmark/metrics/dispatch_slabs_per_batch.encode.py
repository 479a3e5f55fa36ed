"""Slabs per encode dispatch over the window: delta of the scheduler's
slab counter over delta of its batch counter, encode lane."""


def read(run):
    batches = run.delta("SeaweedFS_ec_dispatch_batches", lane="encode")
    if batches <= 0:
        return None
    return run.delta("SeaweedFS_ec_dispatch_slabs", lane="encode") / batches
