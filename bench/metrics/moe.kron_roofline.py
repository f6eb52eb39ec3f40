"""Per cent of their roofline that the routed experts reach: the least
time of their Kron-Matmul work in a step (per projection, the larger of
its FLOPs over the bf16 peak and its least bytes over the HBM bandwidth,
counted on the ``S k`` routed slots and not the padded buckets, forward and
backward without recompute; ``bench/kinds/moe_lm.py``) over the device time
per step in the ``model.moe.experts`` scope.  Padding, recompute, the
SwiGLU's elementwise work and layout ops all show as lost share.  Nothing
when the cell's kind (``bench/kinds/``) hands over no such work or scope."""

from bench.scopes import scope_s, table
from bench.work import least_seconds


def read(run):
    scopes, work = run.work.get("scopes"), run.work.get("expert_kron")
    if not scopes or not work or run.trace is None:
        return None
    s = scope_s(table(run.trace, scopes, ()), scopes, ("model.moe.experts",), False)
    if not s:
        return None
    least = sum(least_seconds(w["flops"], w["bytes"], run.peak)[0] for w in work)
    return 100.0 * least / (s / run.calls)
