"""Share of the HBM roofline reached by the rebuild's reconstruct
dispatches: (survivor bytes read + shard bytes written) over HBM peak, over
device-op time while a rebuild call was in flight. Survivor bytes are the
program's stacked-bytes counter of the reconstruct lane; written bytes are
the rebuilt shard files. None where either is missing."""


def read(run):
    if run.timeline is None:
        return None
    read_b = run.delta("SeaweedFS_ec_dispatch_stacked_bytes_sum",
                       lane="reconstruct")
    written = sum(n for _, _, n in run.host.get("rebuilds") or [])
    busy = sum(run.timeline.busy_in("rebuild", list(range(run.chips))))
    if read_b <= 0 or busy <= 0:
        return None
    return 100.0 * (read_b + written) / run.peaks["hbm_bytes_per_s"] / busy
