"""Mean time a reconstruct slab waited in the dispatch scheduler before
its batch launched, over the window (the program's window-wait histogram,
reconstruct lane)."""


def read(run):
    n = run.delta("SeaweedFS_ec_dispatch_window_wait_seconds_count",
                  lane="reconstruct")
    if n <= 0:
        return None
    return 1e3 * run.delta("SeaweedFS_ec_dispatch_window_wait_seconds_sum",
                           lane="reconstruct") / n
