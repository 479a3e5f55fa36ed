"""Mean duration of the program's `ec.dispatch.encode` spans that started
in the window: one encode batch's pack and coder call, on the thread
that flushed it."""


def read(run):
    ms = run.span_ms("ec.dispatch.encode")
    if not ms:
        return None
    return sum(ms) / len(ms)
