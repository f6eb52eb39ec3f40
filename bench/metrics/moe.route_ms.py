"""Milliseconds per training step that the chip spent routing tokens to the
experts and back: the ``model.moe.router`` (logits, top-k, ranks, balance
loss), ``model.moe.dispatch`` (tokens gathered into buckets) and
``model.moe.combine`` (outputs gathered back and weighted) scopes, with
their backward (device trace joined with the program's scopes,
``bench/scopes.py``, mean over chips).  Nothing when the cell's kind
(``bench/kinds/``) hands over no scope map or the program has none of these scopes."""

from bench.scopes import scope_s, table

SCOPES = ("model.moe.router", "model.moe.dispatch", "model.moe.combine")


def read(run):
    scopes = run.work.get("scopes")
    if not scopes or run.trace is None:
        return None
    s = scope_s(table(run.trace, scopes, ()), scopes, SCOPES, False)
    return None if s is None else s / run.calls * 1e3
