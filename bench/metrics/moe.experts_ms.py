"""Milliseconds per training step that the chip spent in the routed
experts (the ``model.moe.experts`` scope, with what nests in it: the
per-expert Kron kernels, their activations and layout ops, forward,
recompute and backward; device trace joined with the program's scopes,
``bench/scopes.py``, mean over chips).  Nothing when the cell's kind
(``bench/kinds/``) hands over no scope map or the program has no such scope."""

from bench.scopes import scope_s, table


def read(run):
    scopes = run.work.get("scopes")
    if not scopes or run.trace is None:
        return None
    s = scope_s(table(run.trace, scopes, ()), scopes, ("model.moe.experts",), False)
    return None if s is None else s / run.calls * 1e3
