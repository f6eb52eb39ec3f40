"""StageProgram IR + the ONE kernel emitter behind every fused Kron-Matmul path.

Before this module the engine's twelve fused paths — forward / transposed /
backward x single / batched, each written once as a Pallas kernel
(kron_fused.py / kron_fused_t.py) and once as an XLA scan analogue (ops.py) —
were near-duplicate code.  The IR collapses them:

* a ``StageInstr`` is one kernel launch, typed ``multiply`` /
  ``transposed_multiply`` / ``prekron`` and carrying everything the emitter
  needs (``ps, qs, t_m, t_k, t_qs, t_b, direction, acc_dtype``).  ``t_b=None``
  means *unbatched*: batch is just a leading grid axis of size one, not a
  separate code path.
* a ``StageProgram`` is a tuple of instructions; ``transpose(prog)`` derives
  the backward program mechanically (reverse the instructions, flip each
  kind/direction) — no hand-mirrored stage lists anywhere.
* ``run_stage`` / ``run_stage_grad`` / ``run_program`` / ``emit`` interpret
  any program through exactly ONE parameterized Pallas kernel template
  (``_chain_kernel``, plus ``_grad_kernel`` for the factor-gradient stage
  backward) and ONE XLA ``lax.scan`` executor (``_chain_xla`` / ``_grad_xla``).

Planner lowering lives in ``core.autotune.lower`` (KronPlan -> StageProgram);
this module is deliberately core-free so both layers can import it.

Per-stage heterogeneity is first-class: every instruction carries its own
``(p_i, q_i)`` list and its own ``acc_dtype``, so mixed-shape chains like
``ps=(8, 16, 32)`` and per-stage accumulation policies flow through planning,
emission, and the VJP without new code paths.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import chaos, guard, telemetry
from repro.runtime.guard import LoweringError, VmemOverflowError

# Usable-VMEM budget of one kernel, in f32 elements (64 MiB): half of the
# v5e's 128 MiB, counted after tile padding by ``chain_vmem_bytes``.  The
# compiler's own limit is set to three quarters of the chip's VMEM
# (``_compiler_params``), which leaves room for what the model leaves out.
VMEM_BUDGET_ELEMS = 16 * 1024 * 1024

# CPU cache budget for the scan-fused XLA executor (the L2/L3 analogue of the
# Pallas kernels' VMEM budget): chains whose whole working set fits are run
# UNTILED — one set of full-size GEMMs beats a serializing scan when nothing
# spills (measured: the B=8, M=64, (16,16)^3 batched chain is ~1.8x faster
# untiled, while the M=256, (16,16)^4 fig_bwd chain at 64 MB still tiles).
XLA_CACHE_BUDGET_BYTES = 16 * 1024 * 1024

MULTIPLY = "multiply"
TRANSPOSED_MULTIPLY = "transposed_multiply"
PREKRON = "prekron"
_KINDS = (MULTIPLY, TRANSPOSED_MULTIPLY, PREKRON)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    """Resolve ``"auto"``: pallas on TPU, xla elsewhere.

    ``FASTKRON_FORCE_BACKEND=pallas|xla`` overrides the auto rule (explicit
    backends are untouched) — CI's interpret-mode matrix uses it to route
    every auto-dispatched path through the emitted Pallas templates on a
    CPU runner.
    """
    if backend == "auto":
        forced = os.environ.get("FASTKRON_FORCE_BACKEND")
        if forced in ("pallas", "xla"):
            return forced
        return "pallas" if _on_tpu() else "xla"
    return backend


def acc_dtype_for(dtype) -> jnp.dtype:
    """f32 accumulation for <=f32 inputs, f64 for f64 (never truncate)."""
    return jnp.promote_types(dtype, jnp.float32)


def _resolve_acc(acc_dtype: str | None, dtype):
    if acc_dtype is None:
        return acc_dtype_for(dtype)
    return jnp.dtype(acc_dtype)


# ---------------------------------------------------------------------------
# The IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StageInstr:
    """One kernel launch of a stage program.

    ``ps``/``qs`` are the per-chained-factor dims in APPLICATION order (the
    factor applied first is entry 0).  ``kind`` selects the data flow:
    ``multiply`` chains sliced multiplies, ``transposed_multiply`` un-applies
    them (the input cotangent), ``prekron`` first combines the stage's
    factors into their explicit Kronecker product and applies it as one
    sliced multiply (forward or transposed per ``direction``).

    Tiling: ``t_m`` rows, ``t_k`` input columns (a multiple of ``prod(ps)``;
    None = full), ``t_qs`` per-factor Q-tiles, ``t_b`` samples per block —
    ``t_b=None`` means unbatched, executed as a batch-of-one grid.
    ``acc_dtype`` (a dtype name, e.g. ``"float32"``) is this stage's
    accumulation dtype; None promotes the input dtype against f32.
    ``t_m_bwd`` is the planner's tuned M-tile for the transposed instruction;
    ``transpose()`` swaps it in mechanically.
    """

    kind: str
    ps: tuple[int, ...]
    qs: tuple[int, ...]
    factor_ids: tuple[int, ...] = ()
    t_m: int = 8
    t_k: int | None = None
    t_qs: tuple[int, ...] | None = None
    t_b: int | None = None
    direction: str = "fwd"
    acc_dtype: str | None = None
    t_m_bwd: int | None = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown stage kind {self.kind!r}")
        if self.direction not in ("fwd", "bwd"):
            raise ValueError(f"unknown direction {self.direction!r}")
        if len(self.ps) != len(self.qs) or not self.ps:
            raise ValueError(f"ps/qs must be equal-length, non-empty: {self}")
        # kind implies direction for the non-prekron instructions.
        if self.kind == MULTIPLY and self.direction != "fwd":
            object.__setattr__(self, "direction", "fwd")
        if self.kind == TRANSPOSED_MULTIPLY and self.direction != "bwd":
            object.__setattr__(self, "direction", "bwd")

    @property
    def pprod(self) -> int:
        return math.prod(self.ps)

    @property
    def qprod(self) -> int:
        return math.prod(self.qs)

    @property
    def batched(self) -> bool:
        return self.t_b is not None

    def transpose(self) -> "StageInstr":
        """The instruction computing this instruction's input cotangent."""
        if self.kind == PREKRON:
            kind = PREKRON
            direction = "bwd" if self.direction == "fwd" else "fwd"
        elif self.kind == MULTIPLY:
            kind, direction = TRANSPOSED_MULTIPLY, "bwd"
        else:
            kind, direction = MULTIPLY, "fwd"
        return dataclasses.replace(
            self,
            kind=kind,
            direction=direction,
            t_m=self.t_m_bwd if self.t_m_bwd is not None else self.t_m,
            t_m_bwd=self.t_m,
        )

    def describe(self) -> str:
        tag = f"{self.kind}[{list(self.ps)}x{list(self.qs)}]@(t_m={self.t_m},t_k={self.t_k}"
        if self.t_qs is not None:
            tag += f",t_qs={list(self.t_qs)}"
        if self.t_b is not None:
            tag += f",t_b={self.t_b}"
        if self.acc_dtype is not None:
            tag += f",acc={self.acc_dtype}"
        return tag + ")"


@dataclasses.dataclass(frozen=True)
class StageProgram:
    """A planner-emitted sequence of stage instructions.

    ``factor_ids`` on each instruction index into the REVERSED (application
    order) factor list of an ``n_factors``-long chain; ``run_program`` /
    ``emit`` take factors in PROBLEM order and reverse internally.
    """

    instrs: tuple[StageInstr, ...]
    n_factors: int

    def __post_init__(self):
        seen = [i for ins in self.instrs for i in ins.factor_ids]
        if sorted(seen) != list(range(self.n_factors)):
            raise ValueError(
                f"program instrs must cover factors 0..{self.n_factors - 1} "
                f"exactly once, got {seen}"
            )

    @property
    def batched(self) -> bool:
        return any(ins.batched for ins in self.instrs)

    def describe(self) -> str:
        return " -> ".join(ins.describe() for ins in self.instrs)


def transpose(prog: StageProgram) -> StageProgram:
    """The backward program: reversed instructions, each transposed.

    ``emit(transpose(prog))`` computes the input cotangent of ``emit(prog)``
    (the ``jax.vjp`` of the emitted function with respect to ``x``) — this is
    how the engine derives its backward pass instead of hand-mirroring stage
    lists.  ``transpose`` is an involution up to tile hints.
    """
    return StageProgram(
        tuple(ins.transpose() for ins in reversed(prog.instrs)), prog.n_factors
    )


# ---------------------------------------------------------------------------
# Batch-polymorphic primitive bodies (the deduped `_sliced_body*` family)
# ---------------------------------------------------------------------------

# Every GEMM of both executors runs at full input precision: on a TPU the
# default for f32 operands is one bf16 pass, ~1e-3 relative error.
_PREC = jax.lax.Precision.HIGHEST


def _dot(a, b, dims, acc):
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=acc, precision=_PREC
    )


def sliced_apply(y: jax.Array, f: jax.Array, acc_dtype=None) -> jax.Array:
    """One FastKron sliced multiply, batch-polymorphic.

    ``y: (M, S*P)`` with ``f: (P, Q)`` -> ``(M, Q*S)``; or ``y: (B, M, S*P)``
    with per-sample ``f: (B, P, Q)`` -> ``(B, M, Q*S)``.  A 3-D ``y`` with a
    shared 2-D ``f`` folds the batch into rows (pure row-parallelism).
    """
    acc = _resolve_acc(None, y.dtype) if acc_dtype is None else acc_dtype
    if f.ndim == 2:
        if y.ndim == 3:
            b, m, k = y.shape
            return sliced_apply(y.reshape(b * m, k), f, acc).reshape(b, m, -1)
        m, k = y.shape
        p, q = f.shape
        s = k // p
        out = _dot(y.reshape(m * s, p), f, (((1,), (0,)), ((), ())), acc)
        return (
            jnp.swapaxes(out.reshape(m, s, q), 1, 2).reshape(m, q * s)
            .astype(y.dtype)
        )
    b, m, k = y.shape
    p, q = int(f.shape[1]), int(f.shape[2])
    s = k // p
    out = _dot(y.reshape(b, m * s, p), f, (((2,), (1,)), ((0,), (0,))), acc)
    return (
        jnp.swapaxes(out.reshape(b, m, s, q), 2, 3).reshape(b, m, q * s)
        .astype(y.dtype)
    )


def sliced_apply_t(g: jax.Array, f: jax.Array, acc_dtype=None) -> jax.Array:
    """Transposed sliced multiply (the input cotangent), batch-polymorphic.

    ``g: (M, Q*S)`` with ``f: (P, Q)`` -> ``(M, S*P)``; batched analogue with
    3-D ``g``/``f`` as in ``sliced_apply``.
    """
    acc = _resolve_acc(None, g.dtype) if acc_dtype is None else acc_dtype
    if f.ndim == 2:
        if g.ndim == 3:
            b, m, l = g.shape
            return sliced_apply_t(g.reshape(b * m, l), f, acc).reshape(b, m, -1)
        m, l = g.shape
        p, q = f.shape
        s = l // q
        out = _dot(
            jnp.swapaxes(g.reshape(m, q, s), 1, 2).reshape(m * s, q),
            jnp.swapaxes(f, 0, 1),
            (((1,), (0,)), ((), ())),
            acc,
        )
        return out.reshape(m, s * p).astype(g.dtype)
    b, m, l = g.shape
    p, q = int(f.shape[1]), int(f.shape[2])
    s = l // q
    g2 = jnp.swapaxes(g.reshape(b, m, q, s), 2, 3).reshape(b, m * s, q)
    out = _dot(g2, f, (((2,), (2,)), ((0,), (0,))), acc)
    return out.reshape(b, m, s * p).astype(g.dtype)


def prekron_product(stage_factors: Sequence[jax.Array]) -> jax.Array:
    """Explicit Kronecker product of a stage's factors, batch-polymorphic.

    ``stage_factors`` are in APPLICATION order (rev[i], rev[i+1], ...); the
    explicit product must be formed in PROBLEM order, i.e. kron(rev[i+1],
    rev[i]): ``x @ (A (x) B)`` applies B first.  3-D per-sample factors run a
    vmapped ``jnp.kron`` chain.
    """
    stage_factors = tuple(stage_factors)
    kron = jax.vmap(jnp.kron) if stage_factors[0].ndim == 3 else jnp.kron
    f = stage_factors[-1]
    for g in reversed(stage_factors[:-1]):
        f = kron(f, g)
    return f


# ---------------------------------------------------------------------------
# Slab-sliced execution (the distributed round pipeline's view of a program)
# ---------------------------------------------------------------------------


def effective_slabs(size: int, n_slabs: int) -> int:
    """Clamp a requested slab count to what the axis can actually carry: the
    largest divisor of ``size`` that is ``<= n_slabs``.  Slabs must tile the
    axis exactly — a ragged tail slab would change the per-slab payload and
    break the exact comm-accounting invariant (per-slab all_to_all payloads
    sum to the serial total), so we never allow one.  ``n_slabs <= 1`` (and
    ``size == 0``) degenerate to 1, the serial schedule."""
    n = max(1, min(int(n_slabs), int(size) if size else 1))
    while size % n:
        n -= 1
    return n


def split_slabs(y: jax.Array, n_slabs: int, axis: int = 0) -> list[jax.Array]:
    """Split ``y`` into ``n_slabs`` equal slabs along ``axis``.

    The slabs partition an embarrassingly-parallel axis (rows of a 2-D
    operand, samples of a batched one), so running any stage/chain per slab
    and concatenating is BITWISE-identical to the unsliced run — the property
    the slab-pipelined distributed rounds rely on for their serial-parity
    guarantee.  Callers clamp via ``effective_slabs`` first; a non-dividing
    count here is a programming error."""
    size = int(y.shape[axis])
    if n_slabs <= 1:
        return [y]
    if size % n_slabs:
        raise ValueError(
            f"n_slabs={n_slabs} does not divide axis {axis} of size {size}; "
            f"clamp with effective_slabs first"
        )
    return list(jnp.split(y, n_slabs, axis=axis))


# ---------------------------------------------------------------------------
# VMEM-growth models (shared by the emitter and the planner)
# ---------------------------------------------------------------------------


def fused_growth(
    ps: Sequence[int], qs: Sequence[int], t_qs: Sequence[int] | None = None
) -> float:
    """Max live-set multiplier over chain prefixes, with optional Q-tiling."""
    t_qs = tuple(t_qs) if t_qs is not None else tuple(qs)
    g = 1.0
    pprod = qprod = 1
    for p, tq in zip(ps, t_qs):
        pprod *= p
        qprod *= tq
        g = max(g, qprod / pprod)
    return g


def transposed_growth(
    ps: Sequence[int], qs: Sequence[int], t_qs: Sequence[int] | None = None
) -> float:
    """Max live-set multiplier of the inverse chain, relative to T_K.

    Walking the chain backwards, the per-tile column count goes
    ``prod(t_q)*ts_out -> ... -> t_k``; the max over those states bounds VMEM.
    """
    t_qs = tuple(t_qs) if t_qs is not None else tuple(qs)
    pprod = math.prod(ps)
    cols = math.prod(t_qs) / pprod  # in units of t_k
    g = max(1.0, cols)
    for p, tq in zip(reversed(tuple(ps)), reversed(t_qs)):
        cols = cols / tq * p
        g = max(g, cols)
    return g


def max_n_fused(t_k: int, p: int) -> int:
    """Paper: N_fused = floor(log_P T_K)."""
    n = 0
    while t_k >= p and t_k % p == 0:
        t_k //= p
        n += 1
    return n


# ---------------------------------------------------------------------------
# THE Pallas kernel templates (chain, both directions, batch grid axis)
# ---------------------------------------------------------------------------
#
# Mosaic cannot split the lane axis into pieces narrower than 128, so no
# kernel reshapes a row of x into (slices, P).  Each tile is transposed ONCE
# on entry into a working matrix W whose ROWS carry the stage's Kronecker
# digits and whose LANES carry the tile's rows of x — plus, when the tile's
# slice count ``ts`` is a multiple of 128 ("dense" lanes), its slices:
#
#   not dense:  W = (ts * R, t_m)   rows (slice, digits...), lanes m
#   dense:      W = (R, t_m * ts)   rows (digits...),        lanes (m, slice)
#
# A factor step contracts the MINOR row digit p and writes the new digit q as
# the MAJOR row digit — FastKron's layout rotation, here a leading/sublane
# swap plus one GEMM: (s*p, L) -> (p, s*L) -> F^T @ -> (q*s, L).  When L is
# not a multiple of 128 the lanes cannot absorb s, and the step runs as a
# batched GEMM over s instead.  The transposed step is the exact inverse.

LANE = 128
KERNEL_NAMES = ("kron_chain_fwd", "kron_chain_bwd", "kron_stage_grad")


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublane(itemsize: int) -> int:
    """Rows of one native VMEM tile: 8 for 32-bit dtypes, 16 for 16-bit."""
    return 8 * max(1, 4 // int(itemsize))


def _tiled_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of a buffer once its last two dims are padded to tiles."""
    shape = (1, 1) + tuple(int(d) for d in shape)
    lead = math.prod(shape[:-2])
    return (
        lead * _pad_to(shape[-2], _sublane(itemsize)) * _pad_to(shape[-1], LANE)
        * int(itemsize)
    )


def _divisors(n: int) -> list[int]:
    small = [d for d in range(1, math.isqrt(n) + 1) if n % d == 0]
    return sorted(set(small + [n // d for d in small]))


def _x_to_w(v, ts: int, dense: bool):
    """x-layout tile (t_m, ts*R) (slice major) -> working matrix."""
    t_m, t_k = v.shape
    if not dense:
        return v.T
    r = t_k // ts
    if r % LANE == 0:  # lane-aligned digits: split lanes, skip the big transpose
        w = jnp.swapaxes(v.reshape(t_m, ts, r), 1, 2)  # (t_m, r, ts)
        return jnp.swapaxes(w, 0, 1).reshape(r, t_m * ts)
    w = jnp.swapaxes(v.T.reshape(ts, r, t_m), 0, 1)  # (r, ts, t_m)
    return jnp.swapaxes(w, 1, 2).reshape(r, t_m * ts)


def _w_to_x(w, t_m: int, ts: int, dense: bool):
    """Inverse of ``_x_to_w``."""
    if not dense:
        return w.T
    r = w.shape[0]
    if r % LANE == 0:
        v = jnp.swapaxes(w.reshape(r, t_m, ts), 0, 1)  # (t_m, r, ts)
        return jnp.swapaxes(v, 1, 2).reshape(t_m, ts * r)
    v = jnp.swapaxes(w.reshape(r, t_m, ts), 1, 2)  # (r, ts, t_m)
    return jnp.swapaxes(v, 0, 1).reshape(ts * r, t_m).T


def _y_to_w(v, ts: int, dense: bool):
    """y-layout tile (t_m, R*ts) or (t_m, R..., ts) (slice minor) -> W."""
    t_m = v.shape[0]
    if not dense:
        return v.T
    r = math.prod(v.shape[1:]) // ts
    return jnp.swapaxes(v.reshape(t_m, r, ts), 0, 1).reshape(r, t_m * ts)


def _w_to_y(w, t_m: int, ts: int, dense: bool):
    """W -> y layout: (t_m, R, ts) when dense, else the flat (t_m, R*ts)."""
    if not dense:
        return w.T
    return jnp.swapaxes(w.reshape(w.shape[0], t_m, ts), 0, 1)


def _step(w, f, acc, merge: bool):
    """Contract the minor row digit p of W (s*p, L) with f (p, q): (q*s, L).

    ``merge``: fold s into the lanes for one 2-D GEMM — legal in Mosaic only
    for lane-aligned L (``_merges``); otherwise a GEMM batched over s."""
    r, lanes = w.shape
    p, q = f.shape
    s = r // p
    if merge:
        z = jnp.swapaxes(w.reshape(s, p, lanes), 0, 1).reshape(p, s * lanes)
        return _dot(f, z, (((0,), (0,)), ((), ())), acc).reshape(q * s, lanes)
    ft = jnp.broadcast_to(f.T, (s, q, p))
    o = _dot(ft, w.reshape(s, p, lanes), (((2,), (1,)), ((0,), (0,))), acc)
    return jnp.swapaxes(o, 0, 1).reshape(q * s, lanes)


def _step_t(g, f, acc, merge: bool, u=None):
    """Transposed step: contract the major row digit q of G (q*s, L) with
    f (p, q), giving (s*p, L).  With ``u`` (the step's forward input, rows
    (s, p)) also returns the factor gradient sum_{s,L} u[s,p] g[q,s], sharing
    G's relayout between the two GEMMs."""
    r, lanes = g.shape
    p, q = f.shape
    s = r // q
    df = None
    if merge:
        g2 = g.reshape(q, s * lanes)
        if u is not None:
            u2 = jnp.swapaxes(u.reshape(s, p, lanes), 0, 1).reshape(p, s * lanes)
            df = _dot(u2, g2, (((1,), (1,)), ((), ())), acc)
        o = _dot(f, g2, (((1,), (0,)), ((), ())), acc)
        out = jnp.swapaxes(o.reshape(p, s, lanes), 0, 1).reshape(s * p, lanes)
    else:
        g3 = jnp.swapaxes(g.reshape(q, s, lanes), 0, 1)  # (s, q, L)
        if u is not None:
            df = _dot(
                u.reshape(s, p, lanes), g3, (((2,), (2,)), ((0,), (0,))), acc
            ).sum(axis=0)
        fb = jnp.broadcast_to(f, (s, p, q))
        out = _dot(fb, g3, (((2,), (1,)), ((0,), (0,))), acc).reshape(s * p, lanes)
    return out if u is None else (df, out)


def _merges(lanes: int, interpret: bool) -> bool:
    """Whether a factor step folds its slices into the lanes (one 2-D GEMM).
    Interpreted, always: there is no lane constraint, and the 2-D GEMM sums
    in the same order as the XLA executor."""
    return interpret or lanes % LANE == 0


def _chain_kernel(
    x_ref, *refs, n: int, ts: int, dense: bool, direction: str, acc_dtype,
    interpret: bool,
):
    """One parameterized kernel body for every fused chain.

    Blocks carry a leading batch axis (size 1 when the instruction is
    unbatched); samples of a block are walked one at a time, each against its
    own factor slice.  ``direction="fwd"`` chains the factors (f_refs[0]
    first); ``"bwd"`` inverts the chain and accumulates partial dX tiles over
    the sequential Q-tile grid axis.
    """
    f_refs, y_ref = refs[:n], refs[n]
    t_m = x_ref.shape[1]
    jq = pl.program_id(3) if direction == "bwd" else None
    merge = _merges(t_m * ts if dense else t_m, interpret)

    def sample(ib, carry):
        fs = [f_ref[ib].astype(acc_dtype) for f_ref in f_refs]
        if direction == "fwd":
            w = _x_to_w(x_ref[ib].astype(acc_dtype), ts, dense)
            for f in fs:
                w = _step(w, f, acc_dtype, merge)
            y = _w_to_y(w, t_m, ts, dense).reshape(y_ref.shape[1:])
            y_ref[ib] = y.astype(y_ref.dtype)
            return carry
        w = _y_to_w(x_ref[ib].astype(acc_dtype), ts, dense)
        for f in reversed(fs):
            w = _step_t(w, f, acc_dtype, merge)
        # y_ref is acc_dtype (cast to the input dtype by the wrapper) so the
        # cross-Q-tile accumulation never rounds through a low-precision type.
        part = _w_to_x(w, t_m, ts, dense).astype(y_ref.dtype)

        @pl.when(jq == 0)
        def _init():
            y_ref[ib] = part

        @pl.when(jq > 0)
        def _acc():
            y_ref[ib] += part

        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], sample, 0)


def _q_tiling(qs, t_qs, n):
    nq = tuple(q // t for q, t in zip(qs, t_qs))
    strides = [1] * n
    for i in range(1, n):
        strides[i] = strides[i - 1] * nq[i - 1]

    def q_digit(jq, i):
        return (jq // strides[i]) % nq[i]

    return math.prod(nq), q_digit


def _states(ps, qs, direction):
    """Row counts R of the working matrix through the chain (per slice)."""
    r = math.prod(ps) if direction == "fwd" else math.prod(qs)
    out = [r]
    pairs = zip(ps, qs) if direction == "fwd" else reversed(list(zip(ps, qs)))
    for p, q in pairs:
        r = r // p * q if direction == "fwd" else r // q * p
        out.append(r)
    return out


def _work_bytes(r: int, t_m: int, ts: int, dense: bool, acc_bytes: int) -> int:
    shape = (r, t_m * ts) if dense else (r * ts, t_m)
    return _tiled_bytes(shape, acc_bytes)


def _step_bytes(r_in, p, q, t_m, ts, dense, acc_bytes) -> int:
    """Live VMEM of one factor step: its input and output working matrices
    plus the relayout / broadcast temporaries of the GEMM it lowers to."""
    r_out = r_in // p * q
    w_in = _work_bytes(r_in, t_m, ts, dense, acc_bytes)
    w_out = _work_bytes(r_out, t_m, ts, dense, acc_bytes)
    lanes = t_m * ts if dense else t_m
    if lanes % LANE == 0:
        return 2 * (w_in + w_out)
    s = (r_in if dense else r_in * ts) // p
    temps = s * (
        _tiled_bytes((p, lanes), acc_bytes)
        + _tiled_bytes((q, p), acc_bytes)
        + 2 * _tiled_bytes((q, lanes), acc_bytes)
    )
    return w_in + w_out + temps


def chain_vmem_bytes(
    t_b: int, t_m: int, t_k: int, ps, qs, *, direction: str, flat: bool,
    in_bytes: int, out_bytes: int, acc_bytes: int = 4, grad: bool = False,
) -> int:
    """VMEM one grid step of the chain (or stage-gradient) kernel needs, with
    every buffer padded to (sublane, 128) tiles: the double-buffered blocks
    the pipeline streams, plus the live working set of one sample (blocks are
    walked a sample at a time).  The emitter's legality check and the planner
    both read this one model."""
    ps, qs = tuple(ps), tuple(qs)
    pprod, qprod = math.prod(ps), math.prod(qs)
    ts = t_k // pprod
    dense = not flat or ts % LANE == 0
    x_blk = _tiled_bytes((t_m, t_k), in_bytes)
    y_blk = _tiled_bytes((t_m, qprod * ts) if flat else (t_m, qprod, ts), in_bytes)
    f_blk = sum(_tiled_bytes((p, q), in_bytes) for p, q in zip(ps, qs))
    x_acc = _tiled_bytes((t_m, t_k), acc_bytes)
    y_acc = _tiled_bytes((t_m, qprod * ts), acc_bytes)
    if grad:
        blocks = 2 * x_blk + y_blk + f_blk + x_acc + f_blk * acc_bytes // in_bytes
        states = _states(ps, qs, "fwd")
        kept = sum(_work_bytes(r, t_m, ts, dense, acc_bytes) for r in states)
        steps = max(
            _step_bytes(r, p, q, t_m, ts, dense, acc_bytes)
            for r, p, q in zip(states, ps, qs)
        )
        live = kept + steps + x_acc + y_acc
    else:
        if direction == "fwd":
            blocks = x_blk + f_blk + _tiled_bytes(
                (t_m, qprod * ts) if flat else (t_m, qprod, ts), out_bytes
            )
            pairs = list(zip(_states(ps, qs, "fwd"), ps, qs))
        else:
            blocks = y_blk + f_blk + _tiled_bytes((t_m, t_k), out_bytes)
            states = _states(ps, qs, "bwd")
            pairs = list(zip(states, reversed(qs), reversed(ps)))
        steps = max(
            _step_bytes(r, a, b, t_m, ts, dense, acc_bytes) for r, a, b in pairs
        )
        live = steps + x_acc + y_acc
    return 2 * t_b * blocks + live


def tpu_block_error(
    b: int, m: int, k: int, ps, qs, t_m: int, t_k: int, t_qs, itemsize: int
) -> str | None:
    """Why a chain tiling is not a legal Mosaic kernel, or None when it is.

    A block's last two dims must be tile multiples or the full extent: rows
    ``t_m`` a multiple of the sublane tile (or all of M), and the slice
    count ``ts = t_k / prod(P)`` of a K-tiled block a multiple of 128 — the
    output block then is (t_m, prod(Q), ts).  The kernels tile Q only in
    interpret mode (a Q-tiled output block has no legal relayout)."""
    pprod = math.prod(ps)
    ts, s_out = t_k // pprod, k // pprod
    if t_m != m and t_m % _sublane(itemsize):
        return f"t_m={t_m} is neither M={m} nor a multiple of {_sublane(itemsize)}"
    if ts != s_out and ts % LANE:
        return f"K-tile slice count ts={ts} is not a multiple of {LANE}"
    if t_qs is not None and tuple(t_qs) != tuple(qs):
        return f"Q-tiled blocks t_qs={tuple(t_qs)} have no Mosaic layout"
    return None


def legal_tiles(
    direction: str, b: int, m: int, k: int, ps, qs, *, t_b: int, t_m: int,
    itemsize: int, acc_bytes: int = 4, grad: bool = False,
    budget_bytes: int | None = None,
) -> tuple[int, int, int] | None:
    """The (t_b, t_m, t_k) a chain (or stage-gradient) kernel runs with on
    the chip for a (b, m, k) problem, or None when no legal tiling fits the
    VMEM budget — the stage then runs on the XLA executor.

    Legal rows are M or multiples of the sublane tile; legal K-tiles are K
    or ``prod(P) * ts`` with ts a multiple of 128.  Among those that fit,
    prefer lane-dense working matrices, then the most work per grid step (up
    to 2^18 elements: past that the pipeline has few steps to overlap DMA
    with, and the unrolled in-kernel relayouts compile slowly), then rows
    closest to the planner's ``t_m``."""
    budget = VMEM_BUDGET_ELEMS * 4 if budget_bytes is None else budget_bytes
    pprod = math.prod(ps)
    s_out = k // pprod
    t_ms = [d for d in _divisors(m) if d % _sublane(itemsize) == 0 or d == m]
    t_ks = sorted({k} | {pprod * d for d in _divisors(s_out) if d % LANE == 0})
    t_bs = [d for d in _divisors(b) if d <= max(1, t_b)]
    best, best_key = None, None
    for tm in t_ms:
        for tk in t_ks:
            ts = tk // pprod
            flat = tk == k
            for tb in t_bs:
                nbytes = chain_vmem_bytes(
                    tb, tm, tk, ps, qs, direction=direction, flat=flat,
                    in_bytes=itemsize, out_bytes=itemsize, acc_bytes=acc_bytes,
                    grad=grad,
                )
                if nbytes > budget:
                    continue
                lanes = tm * ts if (not flat or ts % LANE == 0) else tm
                key = (
                    lanes % LANE == 0,
                    min(tb * tm * tk, 1 << 18),
                    -abs(math.log2(tm / max(1, t_m))),
                    -tb * tm * tk,
                )
                if best_key is None or key > best_key:
                    best, best_key = (tb, tm, tk), key
    return best


def _compiler_params(interpret: bool):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    from . import hardware

    limit = hardware.tpu_spec().vmem_bytes * 3 // 4
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))


def _check_tiles(b, m, k, ps, qs, t_b, t_m, t_k, t_qs, itemsize, interpret):
    pprod = math.prod(ps)
    if t_k % pprod:
        raise LoweringError(f"T_K={t_k} must be a multiple of prod(P)={pprod}")
    if b % t_b or m % t_m or k % t_k:
        raise LoweringError(
            f"tiles must divide dims: {(b, m, k)} vs {(t_b, t_m, t_k)}"
        )
    if not interpret:
        why = tpu_block_error(b, m, k, ps, qs, t_m, t_k, t_qs, itemsize)
        if why is not None:
            raise LoweringError(f"illegal TPU block: {why}")


@functools.partial(
    jax.jit,
    static_argnames=(
        "t_b", "t_m", "t_k", "t_qs", "direction", "interpret", "acc_dtype",
        "vmem_budget_elems",
    ),
)
def chain_pallas(
    x: jax.Array,
    *factors: jax.Array,
    t_b: int = 1,
    t_m: int = 8,
    t_k: int | None = None,
    t_qs: tuple[int, ...] | None = None,
    direction: str = "fwd",
    interpret: bool = False,
    acc_dtype: str | None = None,
    vmem_budget_elems: int = VMEM_BUDGET_ELEMS,
) -> jax.Array:
    """The single Pallas entry point for any chain instruction.

    ``x: (B, M, C)``; each factor ``(B, P_i, Q_i)`` (B=1 replays the
    unbatched kernels).  ``direction="fwd"``: C = K, returns the
    ``(B, M, prod(Q) * K/prod(P))`` chain output.  ``direction="bwd"``:
    ``x`` is the cotangent at C = prod(Q)*S, returns dX ``(B, M, prod(P)*S)``.
    The grid is always ``(B/t_b, M/t_m, Q-tiles, K/t_k)`` (Q-tiles innermost
    for "bwd": the sequential accumulation axis).  Compiled (not
    interpreted), the tiling must pass ``tpu_block_error``.
    """
    acc = _resolve_acc(acc_dtype, x.dtype)
    b, m, cols = x.shape
    n = len(factors)
    ps = tuple(int(f.shape[1]) for f in factors)
    qs = tuple(int(f.shape[2]) for f in factors)
    for f in factors:
        if int(f.shape[0]) != b:
            raise LoweringError(f"factor batch {f.shape[0]} != x batch {b}")
    pprod = math.prod(ps)
    qprod = math.prod(qs)
    if direction == "fwd":
        if cols % pprod:
            raise LoweringError(f"K={cols} not divisible by prod(P)={pprod}")
        k = cols
    else:
        if cols % qprod:
            raise LoweringError(
                f"dY cols {cols} not divisible by prod(Q)={qprod}"
            )
        k = cols // qprod * pprod
    s_out = k // pprod
    t_b = min(t_b, b)
    t_m = min(t_m, m)
    t_k = min(t_k or k, k)
    if t_qs is None:
        t_qs = qs
    t_qs = tuple(min(t, q) for t, q in zip(t_qs, qs))
    if len(t_qs) != n:
        raise LoweringError(f"t_qs needs one entry per factor: {t_qs} vs {n}")
    if any(q % t for q, t in zip(qs, t_qs)):
        raise LoweringError(f"t_qs must divide factor Q dims: {t_qs} vs {qs}")
    _check_tiles(
        b, m, k, ps, qs, t_b, t_m, t_k, t_qs, x.dtype.itemsize, interpret
    )
    q_full = t_qs == qs
    flat = q_full and t_k == k
    ts = t_k // pprod
    dense = not flat or ts % LANE == 0
    need = chain_vmem_bytes(
        t_b, t_m, t_k, ps, t_qs, direction=direction, flat=flat,
        in_bytes=x.dtype.itemsize, out_bytes=x.dtype.itemsize,
        acc_bytes=jnp.dtype(acc).itemsize,
    )
    if need > vmem_budget_elems * 4:
        raise VmemOverflowError(
            f"tile {t_b}x{t_m}x{t_k} needs {need} B of VMEM (padded), over "
            f"the {vmem_budget_elems * 4} B budget; reduce t_b / t_m / t_k"
        )

    # Composite Q-tile grid axis: one mixed-radix digit per factor, factor 0
    # (applied first) minor — matching the output layout (q_n, ..., q_1, s).
    nq_tiles, q_digit = _q_tiling(qs, t_qs, n)
    # The y-side view: flat (B, M, prod(Q)*S) for whole-K blocks, else
    # (B, M, prod(Q), S) with (t_m, prod(Q), ts) blocks, or — Q-tiled,
    # interpret only — one axis per Q digit, each tiled by its own digit.
    if flat:
        y_view, y_block = (b, m, qprod * s_out), (t_b, t_m, qprod * s_out)

        def y_index(ib, im, jq, j):
            return (ib, im, 0)
    elif q_full:
        y_view, y_block = (b, m, qprod, s_out), (t_b, t_m, qprod, ts)

        def y_index(ib, im, jq, j):
            return (ib, im, 0, j)
    else:
        y_view = (b, m) + tuple(reversed(qs)) + (s_out,)
        y_block = (t_b, t_m) + tuple(reversed(t_qs)) + (ts,)

        def y_index(ib, im, jq, j):
            return (ib, im) + tuple(
                q_digit(jq, i) for i in reversed(range(n))
            ) + (j,)

    kernel = functools.partial(
        _chain_kernel, n=n, ts=ts, dense=dense, direction=direction,
        acc_dtype=acc, interpret=interpret,
    )
    params = _compiler_params(interpret)
    if direction == "fwd":
        grid = (b // t_b, m // t_m, nq_tiles, k // t_k)
        in_specs = [
            pl.BlockSpec((t_b, t_m, t_k), lambda ib, im, jq, j: (ib, im, j))
        ]
        for i in range(n):
            in_specs.append(
                pl.BlockSpec(
                    (t_b, ps[i], t_qs[i]),
                    lambda ib, im, jq, j, i=i: (ib, 0, q_digit(jq, i)),
                )
            )
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(y_block, y_index),
            out_shape=jax.ShapeDtypeStruct(y_view, x.dtype),
            interpret=interpret,
            compiler_params=params,
            name=KERNEL_NAMES[0],
        )(x, *factors)
        return out.reshape(b, m, qprod * s_out)

    # bwd: Q innermost — the sequential accumulation dim.
    grid = (b // t_b, m // t_m, k // t_k, nq_tiles)
    in_specs = [
        pl.BlockSpec(y_block, lambda ib, im, j, jq: y_index(ib, im, jq, j))
    ]
    for i in range(n):
        in_specs.append(
            pl.BlockSpec(
                (t_b, ps[i], t_qs[i]),
                lambda ib, im, j, jq, i=i: (ib, 0, q_digit(jq, i)),
            )
        )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (t_b, t_m, t_k), lambda ib, im, j, jq: (ib, im, j)
        ),
        out_shape=jax.ShapeDtypeStruct((b, m, k), acc),
        interpret=interpret,
        compiler_params=params,
        name=KERNEL_NAMES[1],
    )(x.reshape(y_view), *factors)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The stage-backward Pallas template (dx + factor grads in one launch)
# ---------------------------------------------------------------------------


def _grad_kernel(
    x_ref, dy_ref, *refs, n: int, ts: int, dense: bool, acc_dtype,
    interpret: bool,
):
    """Full stage backward: rematerialize the forward chain in VMEM, then
    walk the transposed chain computing the input gradient and every factor
    gradient.  Per factor ONE relayout of the gradient tile is shared by the
    factor-gradient GEMM and the chain-step GEMM (``_step_t``).  Factor
    grads are per batch block: they accumulate over the (M, K) grid for a
    fixed batch block only (batch is the outermost grid axis, sequential on
    TPU), which reduces to the whole-grid accumulation of the unbatched
    kernel when B = t_b = 1.
    """
    f_refs = refs[:n]
    dx_ref = refs[n]
    df_refs = refs[n + 1 :]
    im, j = pl.program_id(1), pl.program_id(2)
    first = jnp.logical_and(im == 0, j == 0)
    t_m = x_ref.shape[1]
    merge = _merges(t_m * ts if dense else t_m, interpret)

    def sample(ib, carry):
        fs = [f_ref[ib].astype(acc_dtype) for f_ref in f_refs]
        # In-VMEM rematerialization of the forward chain (stage-local).
        us = [_x_to_w(x_ref[ib].astype(acc_dtype), ts, dense)]
        for f in fs[:-1]:
            us.append(_step(us[-1], f, acc_dtype, merge))
        g = _y_to_w(dy_ref[ib].astype(acc_dtype), ts, dense)
        for idx in reversed(range(n)):
            df_part, g = _step_t(g, fs[idx], acc_dtype, merge, u=us[idx])

            @pl.when(first)
            def _init(df_ref=df_refs[idx], df_part=df_part):
                df_ref[ib] = df_part

            @pl.when(jnp.logical_not(first))
            def _acc(df_ref=df_refs[idx], df_part=df_part):
                df_ref[ib] += df_part

        dx_ref[ib] = _w_to_x(g, t_m, ts, dense).astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], sample, 0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "t_b", "t_m", "t_k", "interpret", "acc_dtype", "vmem_budget_elems",
    ),
)
def grad_pallas(
    x: jax.Array,
    dy: jax.Array,
    *factors: jax.Array,
    t_b: int = 1,
    t_m: int = 8,
    t_k: int | None = None,
    interpret: bool = False,
    acc_dtype: str | None = None,
    vmem_budget_elems: int = VMEM_BUDGET_ELEMS,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """The single Pallas stage-backward: (dx, per-factor grads).

    ``x: (B, M, K)`` stage input, ``dy: (B, M, prod(Q)*S)`` stage output
    cotangent, factors ``(B, P_i, Q_i)``; dfs returned in application order,
    each ``(B, P_i, Q_i)``, accumulated in the stage's acc dtype.  B = 1
    replays the unbatched kernel exactly.
    """
    acc = _resolve_acc(acc_dtype, dy.dtype)
    b, m, k = x.shape
    n = len(factors)
    ps = tuple(int(f.shape[1]) for f in factors)
    qs = tuple(int(f.shape[2]) for f in factors)
    for f in factors:
        if int(f.shape[0]) != b:
            raise LoweringError(f"factor batch {f.shape[0]} != x batch {b}")
    pprod = math.prod(ps)
    qprod = math.prod(qs)
    if k % pprod:
        raise LoweringError(f"K={k} not divisible by prod(P)={pprod}")
    s_out = k // pprod
    if dy.shape != (b, m, qprod * s_out):
        raise LoweringError(f"dy shape {dy.shape} != {(b, m, qprod * s_out)}")
    t_b = min(t_b, b)
    t_m = min(t_m, m)
    t_k = min(t_k or k, k)
    _check_tiles(b, m, k, ps, qs, t_b, t_m, t_k, None, x.dtype.itemsize, interpret)
    flat = t_k == k
    ts = t_k // pprod
    dense = not flat or ts % LANE == 0
    need = chain_vmem_bytes(
        t_b, t_m, t_k, ps, qs, direction="fwd", flat=flat,
        in_bytes=x.dtype.itemsize, out_bytes=x.dtype.itemsize,
        acc_bytes=jnp.dtype(acc).itemsize, grad=True,
    )
    if need > vmem_budget_elems * 4:
        raise VmemOverflowError(
            f"bwd tile {t_b}x{t_m}x{t_k} live set needs {need} B of VMEM "
            f"(padded), over the {vmem_budget_elems * 4} B budget; reduce "
            f"t_b / t_k or split the stage"
        )

    grid = (b // t_b, m // t_m, k // t_k)
    if flat:
        dy_view, dy_block = (b, m, qprod * s_out), (t_b, t_m, qprod * s_out)

        def dy_index(ib, im, j):
            return (ib, im, 0)
    else:
        dy_view, dy_block = (b, m, qprod, s_out), (t_b, t_m, qprod, ts)

        def dy_index(ib, im, j):
            return (ib, im, 0, j)

    in_specs = [
        pl.BlockSpec((t_b, t_m, t_k), lambda ib, im, j: (ib, im, j)),
        pl.BlockSpec(dy_block, dy_index),
    ]
    for p, q in zip(ps, qs):
        in_specs.append(pl.BlockSpec((t_b, p, q), lambda ib, im, j: (ib, 0, 0)))
    out_specs = [pl.BlockSpec((t_b, t_m, t_k), lambda ib, im, j: (ib, im, j))]
    out_shapes = [jax.ShapeDtypeStruct((b, m, k), x.dtype)]
    for p, q in zip(ps, qs):
        out_specs.append(pl.BlockSpec((t_b, p, q), lambda ib, im, j: (ib, 0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((b, p, q), acc))
    outs = pl.pallas_call(
        functools.partial(
            _grad_kernel, n=n, ts=ts, dense=dense, acc_dtype=acc,
            interpret=interpret,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
        name=KERNEL_NAMES[2],
    )(x, dy.reshape(dy_view), *factors)
    return outs[0], tuple(outs[1:])


# ---------------------------------------------------------------------------
# THE XLA lax.scan executor (chain, both directions, both batch modes)
# ---------------------------------------------------------------------------


def _chain_max_cols(cols: int, pqs: Sequence[tuple[int, int]]) -> int:
    """Max column count over the chain states starting from ``cols``."""
    mx = cols
    for p, q in pqs:
        cols = cols // p * q
        mx = max(mx, cols)
    return mx


def _xla_tile_rows(m: int, t_m: int, row_bytes: int | None = None) -> int | None:
    """Effective M-tile for the scan-fused XLA path, or None to run untiled.

    Tiling pays off only when the full chain would spill cache
    (``row_bytes``: widest per-row working set) AND the tile chain fits with
    enough tiles to amortize the scan; tiny analytic t_m values (tuned for
    the TPU sublane) are clamped up to a useful CPU tile.
    """
    if row_bytes is not None and m * row_bytes <= XLA_CACHE_BUDGET_BYTES:
        return None
    t = min(m, max(t_m, 8))
    if t >= m or m % t or m // t < 2:
        return None
    return t


def _batch_tile(b: int, t_b: int, sample_bytes: int | None = None) -> int | None:
    """Effective batch tile for the scan-batched XLA path, or None untiled.

    ``sample_bytes``: one sample's chain working set — when the whole batch
    fits the cache budget, run untiled (same rule as ``_xla_tile_rows``).
    """
    if sample_bytes is not None and b * sample_bytes <= XLA_CACHE_BUDGET_BYTES:
        return None
    t = min(b, max(t_b, 1))
    if t >= b or b % t or b // t < 2:
        return None
    return t


def _chain_pqs(factors, direction: str) -> list[tuple[int, int]]:
    """(contract, expand) dims in traversal order for the working-set model."""
    if direction == "fwd":
        return [(int(f.shape[-2]), int(f.shape[-1])) for f in factors]
    return [(int(f.shape[-1]), int(f.shape[-2])) for f in reversed(factors)]


def _chain_apply(y, fs, direction: str, acc) -> jax.Array:
    """The shared chain body: sliced multiplies (fwd) or their transposes in
    reverse (bwd), batch-polymorphic through ``sliced_apply``/``sliced_apply_t``."""
    if direction == "fwd":
        for f in fs:
            y = sliced_apply(y, f, acc)
        return y
    for f in reversed(tuple(fs)):
        y = sliced_apply_t(y, f, acc)
    return y


@functools.partial(
    jax.jit, static_argnames=("t_m", "t_b", "direction", "acc_dtype")
)
def _chain_xla(
    x: jax.Array,
    factors: tuple[jax.Array, ...],
    t_m: int = 8,
    t_b: int | None = None,
    direction: str = "fwd",
    acc_dtype: str | None = None,
) -> jax.Array:
    """The one lax.scan executor: any chain instruction on the XLA backend.

    Unbatched input (2-D ``x``) tiles over M rows; batched input (3-D ``x``
    with 3-D per-sample factors) tiles over B samples.  Either way the whole
    per-tile chain stays cache-resident — the CPU analogue of the Pallas
    kernel's VMEM fusion — and runs UNTILED when the full working set already
    fits ``XLA_CACHE_BUDGET_BYTES``.
    """
    acc = _resolve_acc(acc_dtype, x.dtype)
    maxcols = _chain_max_cols(int(x.shape[-1]), _chain_pqs(factors, direction))
    if x.ndim == 2:
        m, cols = x.shape
        t = _xla_tile_rows(m, t_m, maxcols * x.dtype.itemsize)
        if t is None:
            return _chain_apply(x, factors, direction, acc)
        _, yt = jax.lax.scan(
            lambda _, xt: (None, _chain_apply(xt, factors, direction, acc)),
            None,
            x.reshape(m // t, t, cols),
        )
        return yt.reshape(m, -1)
    b, m, cols = x.shape
    t = _batch_tile(b, t_b or 1, m * maxcols * x.dtype.itemsize)
    if t is None:
        return _chain_apply(x, factors, direction, acc)
    xs = (
        x.reshape(b // t, t, m, cols),
        tuple(f.reshape(b // t, t, *f.shape[1:]) for f in factors),
    )
    _, yt = jax.lax.scan(
        lambda _, xf: (None, _chain_apply(xf[0], xf[1], direction, acc)),
        None,
        xs,
    )
    return yt.reshape(b, m, -1)


def _grad_tile(us_first, g, factors, acc):
    """Backward of one chain tile, batch-polymorphic: shared relayout per
    factor feeds both the factor-gradient GEMM and the chain-step GEMM.
    2-D tiles sum factor grads over rows; 3-D tiles keep them per sample."""
    us = [us_first]
    y = us_first
    for f in factors[:-1]:
        y = sliced_apply(y, f, acc)
        us.append(y)
    dfs = [None] * len(factors)
    cols = g.shape[-1]
    for idx in reversed(range(len(factors))):
        f = factors[idx]
        p, q = int(f.shape[-2]), int(f.shape[-1])
        s = cols // q
        if g.ndim == 2:
            t_m = g.shape[0]
            g2 = jnp.swapaxes(g.reshape(t_m, q, s), 1, 2).reshape(t_m * s, q)
            u2 = us[idx].reshape(t_m * s, p)
            dfs[idx] = _dot(
                u2.astype(acc), g2.astype(acc), (((0,), (0,)), ((), ())), acc
            )
            g = _dot(g2, f, (((1,), (1,)), ((), ())), acc).reshape(
                t_m, s * p
            ).astype(g.dtype)
        else:
            t_b, t_m = g.shape[0], g.shape[1]
            g2 = jnp.swapaxes(g.reshape(t_b, t_m, q, s), 2, 3).reshape(
                t_b, t_m * s, q
            )
            u2 = us[idx].reshape(t_b, t_m * s, p)
            dfs[idx] = _dot(
                u2.astype(acc), g2.astype(acc), (((1,), (1,)), ((0,), (0,))), acc
            )  # (t_b, p, q)
            g = _dot(g2, f, (((2,), (2,)), ((0,), (0,))), acc).reshape(
                t_b, t_m, s * p
            ).astype(g.dtype)
        cols = s * p
    return dfs, g


def _chain_live_cols(k: int, factors) -> int:
    """Backward live set per row: every forward chain state plus the gradient
    at its widest — a sum over chain states, not a max."""
    live = cols = k
    for f in factors:
        cols = cols // int(f.shape[-2]) * int(f.shape[-1])
        live += cols
    return live


@functools.partial(jax.jit, static_argnames=("t_m", "t_b", "acc_dtype"))
def _grad_xla(
    x: jax.Array,
    dy: jax.Array,
    factors: tuple[jax.Array, ...],
    t_m: int = 8,
    t_b: int | None = None,
    acc_dtype: str | None = None,
):
    """The one lax.scan stage-backward executor (dx + factor grads).

    Unbatched: M-tiled scan whose carry SUMS factor grads across row tiles.
    Batched: batch-tiled scan stacking per-sample factor grads.
    """
    acc = _resolve_acc(acc_dtype, dy.dtype)
    if x.ndim == 2:
        m, k = x.shape
        t = _xla_tile_rows(m, t_m, _chain_live_cols(k, factors) * x.dtype.itemsize)
        if t is None:
            dfs, dx = _grad_tile(x, dy, factors, acc)
            return dx, tuple(dfs)

        def body(carry, xg):
            dfs, g = _grad_tile(xg[0], xg[1], factors, acc)
            return tuple(c + d for c, d in zip(carry, dfs)), g

        carry0 = tuple(jnp.zeros(f.shape, acc) for f in factors)
        dfs, dxt = jax.lax.scan(
            body, carry0, (x.reshape(m // t, t, k), dy.reshape(m // t, t, -1))
        )
        return dxt.reshape(m, k), dfs
    b, m, k = x.shape
    t = _batch_tile(
        b, t_b or 1, m * _chain_live_cols(k, factors) * x.dtype.itemsize
    )
    if t is None:
        dfs, dx = _grad_tile(x, dy, factors, acc)
        return dx, tuple(dfs)

    def body(_, xs):
        dfs, g = _grad_tile(xs[0], xs[1], xs[2], acc)
        return None, (g, tuple(dfs))

    xs = (
        x.reshape(b // t, t, m, k),
        dy.reshape(b // t, t, m, -1),
        tuple(f.reshape(b // t, t, *f.shape[1:]) for f in factors),
    )
    _, (dxt, dfts) = jax.lax.scan(body, None, xs)
    return dxt.reshape(b, m, k), tuple(d.reshape(b, *d.shape[2:]) for d in dfts)


# ---------------------------------------------------------------------------
# Instruction / program interpreters (the emitter's public surface)
# ---------------------------------------------------------------------------


def _interpret_default(interpret: bool | None) -> bool:
    return not _on_tpu() if interpret is None else interpret


def _effective(instr: StageInstr, fs: tuple[jax.Array, ...]):
    """(direction, factors, t_qs) after resolving a prekron instruction into
    its explicit product (a chain of one).  A length-1 ``t_qs`` on a prekron
    instruction is the Q-tile of the COMBINED product and survives the
    substitution; per-original-factor tiles do not apply to the product."""
    if instr.kind == PREKRON:
        t_qs = instr.t_qs if instr.t_qs and len(instr.t_qs) == 1 else None
        return instr.direction, (prekron_product(fs),), t_qs
    return instr.direction, fs, instr.t_qs


def stage_tiles(
    instr: StageInstr, y_shape, dtype, *, grad: bool = False,
    vmem_budget_elems: int = VMEM_BUDGET_ELEMS,
) -> tuple[int, int, int] | None:
    """The legal (t_b, t_m, t_k) the COMPILED Pallas kernel runs ``instr``
    with on an operand of ``y_shape`` ((M, C), or (B, M, C) batched), or None
    when no legal tiling fits VMEM and the stage runs on the XLA executor.
    Decided from shapes and dtype alone, before anything is compiled.
    ``grad=True`` asks about the stage-backward kernel (``y_shape`` is then
    the stage input's)."""
    ps, qs = instr.ps, instr.qs
    if instr.kind == PREKRON:
        ps, qs = (math.prod(ps),), (math.prod(qs),)
    b, m, cols = (1,) * (3 - len(y_shape)) + tuple(int(d) for d in y_shape)
    direction = "fwd" if grad else instr.direction
    k = cols if direction == "fwd" else cols // math.prod(qs) * math.prod(ps)
    dtype = jnp.dtype(dtype)
    return legal_tiles(
        direction, b, m, k, ps, qs, t_b=instr.t_b or 1, t_m=instr.t_m,
        itemsize=dtype.itemsize,
        acc_bytes=jnp.dtype(_resolve_acc(instr.acc_dtype, dtype)).itemsize,
        grad=grad, budget_bytes=vmem_budget_elems * 4,
    )


def run_stage(
    y: jax.Array,
    stage_factors: Sequence[jax.Array],
    instr: StageInstr,
    *,
    backend: str = "auto",
    interpret: bool | None = None,
    vmem_budget_elems: int = VMEM_BUDGET_ELEMS,
) -> jax.Array:
    """Execute one chain instruction on ``y``.

    ``stage_factors`` are the stage's factor arrays in application order —
    2-D when ``instr.t_b is None``, per-sample 3-D otherwise.  Compiled,
    the instruction runs with the legal tiles of ``stage_tiles`` (the XLA
    executor when there are none).  Interpreted, it runs with its own tiles
    and raises ``VmemOverflowError`` (a ``ValueError``) when they cannot
    hold the stage in VMEM.
    """
    chaos.maybe_fail("stage_execute")
    with telemetry.scope("stage"):
        fs = tuple(stage_factors)
        direction, fs, t_qs = _effective(instr, fs)
        b = resolve_backend(backend)
        if b == "xla":
            return _chain_xla(
                y, fs, t_m=instr.t_m, t_b=instr.t_b, direction=direction,
                acc_dtype=instr.acc_dtype,
            )
        chaos.maybe_fail("pallas_lowering")
        ip = _interpret_default(interpret)
        t_b, t_m, t_k = instr.t_b or 1, instr.t_m, instr.t_k
        if not ip:
            # Compiled: run the legal tiling (``stage_tiles``), or the XLA
            # executor when the shape has none — never a failed compile.
            tiles = stage_tiles(
                instr, y.shape, y.dtype, vmem_budget_elems=vmem_budget_elems
            )
            if tiles is None:
                return _chain_xla(
                    y, fs, t_m=instr.t_m, t_b=instr.t_b, direction=direction,
                    acc_dtype=instr.acc_dtype,
                )
            (t_b, t_m, t_k), t_qs = tiles, None
        if instr.t_b is None:
            out = chain_pallas(
                y[None], *(f[None] for f in fs), t_b=1, t_m=t_m, t_k=t_k,
                t_qs=t_qs, direction=direction, interpret=ip,
                acc_dtype=instr.acc_dtype, vmem_budget_elems=vmem_budget_elems,
            )
            return out[0]
        return chain_pallas(
            y, *fs, t_b=t_b, t_m=t_m, t_k=t_k, t_qs=t_qs,
            direction=direction, interpret=ip, acc_dtype=instr.acc_dtype,
            vmem_budget_elems=vmem_budget_elems,
        )


def run_stage_grad(
    u: jax.Array,
    g: jax.Array,
    stage_factors: Sequence[jax.Array],
    instr: StageInstr,
    *,
    backend: str = "auto",
    interpret: bool | None = None,
    vmem_budget_elems: int = VMEM_BUDGET_ELEMS,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """Full backward of one forward chain instruction: (dx, factor grads).

    ``u`` is the stage input, ``g`` the stage output cotangent; ``instr`` is
    the FORWARD instruction (its transpose is implied).  Factor grads are
    returned in application order, accumulated in the stage's acc dtype
    (callers cast).  Tiles are chosen as in ``run_stage``; interpreted, it
    raises ``VmemOverflowError`` (a ``ValueError``) when the instruction's
    own tiles cannot hold the stage's live set in VMEM.
    """
    chaos.maybe_fail("stage_execute")
    with telemetry.scope("stage_grad"):
        fs = tuple(stage_factors)
        b = resolve_backend(backend)
        if b == "xla":
            dx, dfs = _grad_xla(
                u, g, fs, t_m=instr.t_m, t_b=instr.t_b,
                acc_dtype=instr.acc_dtype,
            )
            return guard.check_finite(dx, "run_stage_grad"), dfs
        chaos.maybe_fail("pallas_lowering")
        ip = _interpret_default(interpret)
        t_b, t_m, t_k = instr.t_b or 1, instr.t_m, instr.t_k
        if not ip:
            tiles = stage_tiles(
                instr, u.shape, u.dtype, grad=True,
                vmem_budget_elems=vmem_budget_elems,
            )
            if tiles is None:
                dx, dfs = _grad_xla(
                    u, g, fs, t_m=instr.t_m, t_b=instr.t_b,
                    acc_dtype=instr.acc_dtype,
                )
                return guard.check_finite(dx, "run_stage_grad"), dfs
            t_b, t_m, t_k = tiles
        if instr.t_b is None:
            dx, dfs = grad_pallas(
                u[None], g[None], *(f[None] for f in fs), t_b=1, t_m=t_m,
                t_k=t_k, interpret=ip, acc_dtype=instr.acc_dtype,
                vmem_budget_elems=vmem_budget_elems,
            )
            return guard.check_finite(dx[0], "run_stage_grad"), tuple(
                d[0] for d in dfs
            )
        dx, dfs = grad_pallas(
            u, g, *fs, t_b=t_b, t_m=t_m, t_k=t_k, interpret=ip,
            acc_dtype=instr.acc_dtype, vmem_budget_elems=vmem_budget_elems,
        )
        return guard.check_finite(dx, "run_stage_grad"), dfs


def run_program(
    x: jax.Array,
    factors: Sequence[jax.Array],
    prog: StageProgram,
    *,
    backend: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Interpret a StageProgram: walk its instructions over ``x``.

    ``factors`` is the full chain's factor tuple in PROBLEM order (as the
    engine's entry points take it); each instruction selects its stage's
    factors via ``factor_ids`` into the reversed (application-order) list.
    For a transposed program (``transpose(prog)``), ``x`` is the output
    cotangent and the result is the input cotangent.
    """
    factors = tuple(factors)
    if len(factors) != prog.n_factors:
        raise ValueError(
            f"program expects {prog.n_factors} factors, got {len(factors)}"
        )
    rev = tuple(reversed(factors))
    with telemetry.scope("program"):
        y = x
        for instr in prog.instrs:
            y = run_stage(
                y, tuple(rev[i] for i in instr.factor_ids), instr,
                backend=backend, interpret=interpret,
            )
    # Non-finite guard on the program's output — the value downstream layers
    # consume, after every stage's acc_dtype downcast (policy off|warn|raise).
    return guard.check_finite(y, "run_program")


def emit(
    prog: StageProgram, *, backend: str = "auto", interpret: bool | None = None
):
    """Close a StageProgram over a backend: returns ``fn(x, factors)``.

    ``emit(transpose(prog))`` is the x-cotangent of ``emit(prog)`` — the
    property pinned by tests/test_properties.py.
    """

    def fn(x, factors):
        return run_program(x, factors, prog, backend=backend, interpret=interpret)

    return fn


__all__ = [
    "StageInstr",
    "StageProgram",
    "transpose",
    "emit",
    "run_program",
    "run_stage",
    "run_stage_grad",
    "sliced_apply",
    "sliced_apply_t",
    "prekron_product",
    "effective_slabs",
    "split_slabs",
    "chain_pallas",
    "grad_pallas",
    "chain_vmem_bytes",
    "legal_tiles",
    "stage_tiles",
    "tpu_block_error",
    "KERNEL_NAMES",
    "fused_growth",
    "transposed_growth",
    "max_n_fused",
    "acc_dtype_for",
    "resolve_backend",
    "MULTIPLY",
    "TRANSPOSED_MULTIPLY",
    "PREKRON",
    "VMEM_BUDGET_ELEMS",
    "XLA_CACHE_BUDGET_BYTES",
]
