"""Output tokens made in the window, per second of the window and per
chip of the cell."""


def read(run):
    n = sum(len(s.token_times) for s in run.rec.sent)
    return n / run.window_s / run.chips if n else None
