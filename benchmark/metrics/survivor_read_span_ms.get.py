"""Mean duration of the program's `volume.ec.survivor_read` spans that
started in the window: a degraded read's survivor gather, local preads
plus remote fetches."""


def read(run):
    ms = run.span_ms("volume.ec.survivor_read")
    if not ms:
        return None
    return sum(ms) / len(ms)
