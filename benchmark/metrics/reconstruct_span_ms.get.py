"""Mean duration of the program's `volume.ec.reconstruct` spans that started in
the window."""


def read(run):
    ms = run.span_ms("volume.ec.reconstruct")
    if not ms:
        return None
    return sum(ms) / len(ms)
