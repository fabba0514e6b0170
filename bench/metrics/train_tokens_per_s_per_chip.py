"""Tokens every learner consumed in the window, over the window's seconds
and the cell's chips (host clock around the whole window)."""


def read(run):
    c = run.counters
    if "tokens" not in c:
        return None
    return c["tokens"] / run.window_s / c["chips"]
