"""Mean duration of the program's `volume.read` spans that started in
the window."""


def read(run):
    ms = run.span_ms("volume.read")
    if not ms:
        return None
    return sum(ms) / len(ms)
