"""Reconstructed-block cache hits over lookups in the window:
hit / (hit + miss) of the program's recon-cache counter."""


def read(run):
    name = "SeaweedFS_ec_dispatch_recon_cache_ops"
    hit = run.delta(name, result="hit")
    miss = run.delta(name, result="miss")
    if hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)
