"""Set-up seconds: from the start of the process to the opening of the
window: JAX start-up, building the volumes from the seed, starting the
servers, warm-up and any compilation (host clock)."""


def read(run):
    return run.setup_s
