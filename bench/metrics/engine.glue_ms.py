"""Milliseconds per call that the chip spent in operations that are neither
the emitter's kernels nor collectives: the engine's transposes, pads and
copies, and stages run by XLA (device trace, mean over chips)."""


def read(run):
    return run.trace.mean("other_s") / run.calls * 1e3
