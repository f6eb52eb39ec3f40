"""Milliseconds per call (or step) that the chip spent in the emitter's
Pallas kernels (``kron_chain_fwd``, ``kron_chain_bwd``, ``kron_stage_grad``),
averaged over the cell's chips (device trace).  Nothing when no kernel ran."""


def read(run):
    ms = run.trace.mean("kernel_s") / run.calls * 1e3
    return ms if ms > 0 else None
