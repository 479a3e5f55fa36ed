"""The largest share of the window's encode batches that ran on any one
chip (scheduler batch counter by chip, encode lane)."""


def read(run):
    per_chip = run.delta_split("SeaweedFS_ec_dispatch_batches", "chip",
                               lane="encode")
    total = sum(per_chip.values())
    if total <= 0:
        return None
    return 100.0 * max(per_chip.values()) / total
