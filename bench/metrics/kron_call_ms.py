"""Milliseconds per Kron-Matmul call: the window's length, from its first
call's dispatch until every call sent in it has finished, over those calls
(host clock)."""


def read(run):
    return run.window_s / run.calls * 1e3
