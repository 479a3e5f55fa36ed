"""Share of the window's encode batches whose submission started while
another chip's submission of the same scheduler was still under way:
delta of SeaweedFS_ec_dispatch_overlapped_batches{lane=encode} over
delta of SeaweedFS_ec_dispatch_batches{lane=encode}, x 100, all chips
together (program counters). It reads above 0 only where chip lanes
submit under locks of their own. None where the program has no such
counter."""


def read(run):
    batches = run.delta("SeaweedFS_ec_dispatch_batches", lane="encode")
    if batches <= 0 or not any(
            name == "SeaweedFS_ec_dispatch_overlapped_batches"
            for name, _ in run.counters_after):
        return None
    return 100.0 * run.delta("SeaweedFS_ec_dispatch_overlapped_batches",
                             lane="encode") / batches
