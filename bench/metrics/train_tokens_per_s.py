"""Training tokens per second: the tokens of every step sent in the window
over the window's length, from the first step's dispatch until every step
sent has finished (host clock)."""


def read(run):
    return run.work["tokens"] * run.calls / run.window_s
