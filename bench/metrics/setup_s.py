"""Set-up seconds: process start to the first timed call (imports, inputs
and weights made from the seed, compiles or compile-cache loads, warm-up,
and the steps a training cell drives before its window)."""


def read(run):
    return run.setup_s
