"""Jit'd wrappers + backend dispatch for the Kron-Matmul kernels.

Three backends for one sliced multiply / fused chain:

  * ``xla``     — the pure-jnp einsum formulation (kernels/ref.py semantics,
                  but in the input dtype with f32 accumulation).  On CPU this
                  is the fast path; fused chains additionally run as a
                  ``lax.scan`` over M-tiles so the whole per-tile chain stays
                  cache-resident — the CPU analogue of the Pallas kernel's
                  VMEM fusion (see EXPERIMENTS.md §Backward).
  * ``pallas``  — the Pallas TPU kernels.  ``interpret=True`` is forced
                  automatically off-TPU so the same call sites work in this
                  CPU container (correctness validation) and on real hardware
                  (performance).
  * ``auto``    — pallas on TPU, xla elsewhere.

Since the StageProgram refactor the fused-chain execution lives in
``kernels/emit.py`` (one kernel template + one scan executor interpreting
``StageInstr``s); the six ``fused_kron*`` wrappers here are DEPRECATED
compatibility shims that build a one-instruction program and call the
emitter.  Each warns once per process; the engine's hot paths call ``emit``
directly and never enter them.  ``sliced_multiply`` / ``sliced_multiply_t``
remain first-class: they run one-factor chain instructions (the C1/C2
sliced multiply) that the unfused baseline and the distributed
per-iteration mode use.
"""
from __future__ import annotations

import warnings
from typing import Sequence

import jax

from . import emit
from . import ref as _ref
from .emit import XLA_CACHE_BUDGET_BYTES, acc_dtype_for, resolve_backend  # noqa: F401

Backend = str  # "auto" | "xla" | "pallas"


_SHIM_WARNED: set[str] = set()


def warn_shim(name: str) -> None:
    """Emit ONE DeprecationWarning per process per legacy fused_kron* shim."""
    if name in _SHIM_WARNED:
        return
    _SHIM_WARNED.add(name)
    warnings.warn(
        f"kernels.ops.{name} is deprecated: build a StageInstr/StageProgram "
        "and call kernels.emit (run_stage / run_stage_grad); the engine's "
        "planned paths do this automatically.",
        DeprecationWarning,
        stacklevel=3,
    )


_sliced_xla = jax.jit(lambda x, f: emit.sliced_apply(x, f))
_sliced_t_xla = jax.jit(lambda dy, f: emit.sliced_apply_t(dy, f))


def _one_factor(f, tiles, kind) -> emit.StageInstr:
    """A one-factor chain instruction from (t_m, t_s, t_q) paper tiles."""
    p, q = int(f.shape[0]), int(f.shape[1])
    t_m, t_s, t_q = tiles or (8, None, None)
    return emit.StageInstr(
        kind=kind, ps=(p,), qs=(q,), t_m=t_m,
        t_k=None if t_s is None else t_s * p,
        t_qs=None if t_q is None else (t_q,),
    )


def sliced_multiply(
    x: jax.Array,
    f: jax.Array,
    *,
    backend: Backend = "auto",
    tiles: tuple[int, int, int] | None = None,
) -> jax.Array:
    """One FastKron sliced multiply: (M, K) x (P, Q) -> (M, K//P*Q)."""
    b = resolve_backend(backend)
    if b == "xla":
        return _sliced_xla(x, f)
    return emit.run_stage(x, (f,), _one_factor(f, tiles, emit.MULTIPLY), backend=b)


def sliced_multiply_t(
    dy: jax.Array,
    f: jax.Array,
    *,
    backend: Backend = "auto",
    tiles: tuple[int, int, int] | None = None,
) -> jax.Array:
    """Transposed sliced multiply (C1 backward): (M, Q*S) x (P,Q) -> (M, S*P)."""
    b = resolve_backend(backend)
    if b == "xla":
        return _sliced_t_xla(dy, f)
    return emit.run_stage(
        dy, (f,), _one_factor(f, tiles, emit.TRANSPOSED_MULTIPLY), backend=b
    )


# ---------------------------------------------------------------------------
# DEPRECATED fused-chain shims (one StageInstr each, executed by the emitter)
# ---------------------------------------------------------------------------


def _chain_instr(factors, *, kind, t_b=None, t_m=8, t_k=None, t_qs=None):
    off = 0 if t_b is None else 1
    return emit.StageInstr(
        kind=kind,
        ps=tuple(int(f.shape[off]) for f in factors),
        qs=tuple(int(f.shape[off + 1]) for f in factors),
        t_m=t_m, t_k=t_k, t_qs=t_qs, t_b=t_b,
    )


def fused_kron(
    x: jax.Array,
    factors_last_first: Sequence[jax.Array],
    *,
    backend: Backend = "auto",
    t_m: int = 8,
    t_k: int | None = None,
    t_qs: tuple[int, ...] | None = None,
) -> jax.Array:
    """DEPRECATED shim: chain of sliced multiplies in one kernel (C3).

    ``factors_last_first[0] == F^N``.  Equivalent to ``emit.run_stage`` on a
    ``multiply`` instruction.
    """
    warn_shim("fused_kron")
    fs = tuple(factors_last_first)
    instr = _chain_instr(fs, kind=emit.MULTIPLY, t_m=t_m, t_k=t_k, t_qs=t_qs)
    return emit.run_stage(x, fs, instr, backend=backend)


def fused_kron_t(
    dy: jax.Array,
    factors_last_first: Sequence[jax.Array],
    *,
    backend: Backend = "auto",
    t_m: int = 8,
    t_k: int | None = None,
    t_qs: tuple[int, ...] | None = None,
) -> jax.Array:
    """DEPRECATED shim: transposed fused chain (input cotangent of
    ``fused_kron``); a ``transposed_multiply`` instruction on the emitter.

    Takes the SAME factor list as the forward call and un-applies the chain
    (last-applied factor's transpose first).
    """
    warn_shim("fused_kron_t")
    fs = tuple(factors_last_first)
    instr = _chain_instr(
        fs, kind=emit.TRANSPOSED_MULTIPLY, t_m=t_m, t_k=t_k, t_qs=t_qs
    )
    return emit.run_stage(dy, fs, instr, backend=backend)


def fused_kron_bwd(
    x: jax.Array,
    dy: jax.Array,
    factors_last_first: Sequence[jax.Array],
    *,
    backend: Backend = "auto",
    t_m: int = 8,
    t_k: int | None = None,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """DEPRECATED shim: full backward of one fused stage (dx, factor grads)
    via ``emit.run_stage_grad``.

    x is the stage input, dy the stage output cotangent; factor grads are
    returned in ``factors_last_first`` order, accumulated in f32 (callers
    cast).
    """
    warn_shim("fused_kron_bwd")
    fs = tuple(factors_last_first)
    instr = _chain_instr(fs, kind=emit.MULTIPLY, t_m=t_m, t_k=t_k)
    return emit.run_stage_grad(x, dy, fs, instr, backend=backend)


def fused_kron_batched(
    x: jax.Array,
    factors_last_first: Sequence[jax.Array],
    *,
    backend: Backend = "auto",
    t_b: int = 1,
    t_m: int = 8,
    t_k: int | None = None,
    t_qs: tuple[int, ...] | None = None,
) -> jax.Array:
    """DEPRECATED shim: batched fused chain — x (B, M, K), per-sample factors
    (B, P_i, Q_i) — via a batched ``multiply`` instruction."""
    warn_shim("fused_kron_batched")
    fs = tuple(factors_last_first)
    instr = _chain_instr(
        fs, kind=emit.MULTIPLY, t_b=t_b, t_m=t_m, t_k=t_k, t_qs=t_qs
    )
    return emit.run_stage(x, fs, instr, backend=backend)


def fused_kron_t_batched(
    dy: jax.Array,
    factors_last_first: Sequence[jax.Array],
    *,
    backend: Backend = "auto",
    t_b: int = 1,
    t_m: int = 8,
    t_k: int | None = None,
    t_qs: tuple[int, ...] | None = None,
) -> jax.Array:
    """DEPRECATED shim: batched transposed fused chain (input cotangent of
    ``fused_kron_batched``)."""
    warn_shim("fused_kron_t_batched")
    fs = tuple(factors_last_first)
    instr = _chain_instr(
        fs, kind=emit.TRANSPOSED_MULTIPLY, t_b=t_b, t_m=t_m, t_k=t_k, t_qs=t_qs
    )
    return emit.run_stage(dy, fs, instr, backend=backend)


def fused_kron_bwd_batched(
    x: jax.Array,
    dy: jax.Array,
    factors_last_first: Sequence[jax.Array],
    *,
    backend: Backend = "auto",
    t_b: int = 1,
    t_m: int = 8,
    t_k: int | None = None,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """DEPRECATED shim: batched full stage backward — per-sample (dx, factor
    grads each (B, P_i, Q_i)) — via ``emit.run_stage_grad``."""
    warn_shim("fused_kron_bwd_batched")
    fs = tuple(factors_last_first)
    instr = _chain_instr(fs, kind=emit.MULTIPLY, t_b=t_b, t_m=t_m, t_k=t_k)
    return emit.run_stage_grad(x, dy, fs, instr, backend=backend)


# Re-export the oracles so tests can import one module.
sliced_multiply_ref = _ref.sliced_multiply_ref
fused_kron_ref = _ref.fused_kron_ref
sliced_multiply_t_ref = _ref.sliced_multiply_t_ref
fused_kron_t_ref = _ref.fused_kron_t_ref

__all__ = [
    "sliced_multiply",
    "sliced_multiply_t",
    "fused_kron",
    "fused_kron_t",
    "fused_kron_bwd",
    "fused_kron_batched",
    "fused_kron_t_batched",
    "fused_kron_bwd_batched",
    "resolve_backend",
    "acc_dtype_for",
    "sliced_multiply_ref",
    "sliced_multiply_t_ref",
    "fused_kron_ref",
    "fused_kron_t_ref",
]
