"""One FastKron sliced multiply (contributions C1+C2) as a chain of one.

Semantics: for ``X: (M, K)`` and ``F: (P, Q)`` with ``S = K // P`` compute

    Y[m, q*S + s] = sum_p X[m, s*P + p] * F[p, q]

The kernel is the emitter's chain template (``emit.chain_pallas``) with a
single factor: its output block is the (T_M, Q, T_S) tile of the FastKron
layout, so the strided scatter of the CUDA kernel is a contiguous block
store.  Tiling mirrors the paper's {T_M, T_K, T_Q} thread-block tile:
``t_s`` slices per block (T_K = t_s * P) and ``t_q`` columns of F.
"""
from __future__ import annotations

import jax

from . import emit


def sliced_multiply_pallas(
    x: jax.Array,
    f: jax.Array,
    *,
    t_m: int = 8,
    t_s: int | None = None,
    t_q: int | None = None,
    interpret: bool = False,
    acc_dtype=None,
) -> jax.Array:
    """Single sliced multiply.  ``x: (M, K)``, ``f: (P, Q)`` -> (M, Q*S)."""
    p, q = f.shape
    if x.shape[1] % p:
        raise ValueError(f"K={x.shape[1]} not divisible by P={p}")
    s = x.shape[1] // p
    t_s = min(t_s or max(1, min(s, 512)), s)
    return emit.chain_pallas(
        x[None], f[None], t_m=t_m, t_k=t_s * p,
        t_qs=None if t_q is None else (t_q,), interpret=interpret,
        acc_dtype=acc_dtype,
    )[0]


__all__ = ["sliced_multiply_pallas"]
