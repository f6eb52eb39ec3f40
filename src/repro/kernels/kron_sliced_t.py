"""The TRANSPOSED sliced multiply — the backward of FastKron's C1
(beyond-paper: the paper only treats inference/forward).

The VJP of ``Y[m, q*S+s] = sum_p X[m, s*P+p] F[p, q]`` w.r.t. X is

    dX[m, s*P + p] = sum_q dY[m, q*S + s] * F[p, q]

which is the emitter's transposed chain of one factor
(``emit.chain_pallas(direction="bwd")``): dY blocks are read in the FastKron
layout, dX written as contiguous (T_M, T_S*P) tiles, and Q-tiles (``t_q``)
accumulate across the innermost, sequential grid axis.
"""
from __future__ import annotations

import jax

from . import emit


def sliced_multiply_t_pallas(
    dy: jax.Array,
    f: jax.Array,
    *,
    t_m: int = 8,
    t_s: int | None = None,
    t_q: int | None = None,
    interpret: bool = False,
    acc_dtype=None,
) -> jax.Array:
    """dX for one sliced multiply.  dy: (M, Q*S), f: (P, Q) -> (M, S*P)."""
    p, q = f.shape
    if dy.shape[1] % q:
        raise ValueError(f"dY cols {dy.shape[1]} not divisible by Q={q}")
    s = dy.shape[1] // q
    t_s = min(t_s or max(1, min(s, 512)), s)
    return emit.chain_pallas(
        dy[None], f[None], t_m=t_m, t_k=t_s * p,
        t_qs=None if t_q is None else (t_q,), direction="bwd",
        interpret=interpret, acc_dtype=acc_dtype,
    )[0]


__all__ = ["sliced_multiply_t_pallas"]
