"""Per cent of its roofline that a Kron-Matmul call reaches: the least time
of the call's work on one chip (the larger of its algorithmic FLOPs over
the bf16 peak and its least bytes over the HBM bandwidth; ``bench/work.py``)
over the chip's busy time per call (device trace, mean over chips)."""

from bench.work import least_seconds


def read(run):
    busy = run.trace.busy_s / run.calls
    if busy <= 0:
        return None
    least, _ = least_seconds(run.work["flops"], run.work["bytes"], run.peak)
    return 100.0 * least / busy
