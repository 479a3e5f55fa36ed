"""Mean duration of the program's `ec.dispatch.wait` spans that started in
the window: a degraded read's wait on the dispatch scheduler, from
submit until the rows are host bytes."""


def read(run):
    ms = run.span_ms("ec.dispatch.wait")
    if not ms:
        return None
    return sum(ms) / len(ms)
