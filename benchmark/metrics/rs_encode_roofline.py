"""Share of the HBM roofline reached by the encode dispatches.

Least time: each dispatch reads k data rows and writes m parity rows of
its width W, (k+m)*W bytes whatever formulation runs; k*W summed over the
window's encode dispatches is the program's stacked-bytes counter, and the
widths are taken before any padding. GF(2^8) arithmetic has no entry in
the peak table, so HBM bandwidth bounds it. Kernel time: device-op time on
the cell's chips while an encode call was in flight (compute ops only, so
host-device transfers are left out). None where either is missing."""


def read(run):
    if run.timeline is None:
        return None
    k, m = run.config["data_shards"], run.config["parity_shards"]
    data = run.delta("SeaweedFS_ec_dispatch_stacked_bytes_sum",
                     lane="encode")
    busy = sum(run.timeline.busy_in("encode", list(range(run.chips))))
    if data <= 0 or busy <= 0:
        return None
    return 100.0 * data * (k + m) / k / run.peaks["hbm_bytes_per_s"] / busy
