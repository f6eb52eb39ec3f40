"""KronOp: the unified, handle-based Kron-Matmul execution engine.

The FastKron paper ships its library as a handle API (init -> size query ->
tuned execute) because Kron-Matmul performance lives in a *plan* that should
be resolved once and reused across calls.  ``KronOp`` is that handle for this
repro: constructed once from the problem signature, it resolves its
``KronPlan`` (and, on a mesh, the communication round schedule) up front and
owns the custom-VJP closures, so repeated calls never re-enter plan memo
lookups.  The four legacy entry points (``kron_matmul``,
``kron_matmul_batched``, ``kron_matmul_distributed``,
``kron_matmul_batched_distributed``) are thin deprecation shims over this
one dispatch spine — two orthogonal axes, (local | mesh) x (single |
batched), instead of four parallel code paths.

    op = KronOp((16, 16), (16, 16))          # plan resolved here
    y = op(x, factors)                       # planned fwd + plan-driven VJP
    op_b = op.with_batch(8, shared_factors=False)
    op_d = op.with_mesh(mesh)                # round schedule resolved here

Since the StageProgram refactor the spine is **program-driven end to end**:
a resolved ``KronPlan`` is lowered once (``autotune.lower``, memoized in
``_lowered``) into a ``kernels.emit.StageProgram``, the forward walks its
instructions through the ONE kernel emitter (``emit.run_stage``), and the
backward executes ``emit.transpose`` of the forward program — the twelve
near-duplicate fused paths (fwd/transposed/bwd x single/batched x
Pallas/XLA) and the hand-mirrored ``_*_batched`` twins this module used to
carry are gone; batchedness lives in the program's ``t_b`` and the operand
ranks, not in parallel code.

Execution is expressed through two JAX primitives, ``kron_matmul_p`` and
``kron_matmul_batched_p``, whose **custom batching rules** are what make
``jax.vmap`` a first-class consumer: ``vmap`` over ``x`` alone collapses the
batch into the row axis (shared factors are a pure row-parallel problem),
while ``vmap`` over ``(x, factors)`` re-binds the batched primitive so the
PR-2 batch-grid kernels run instead of the generic per-op batching fallback
(the ROADMAP's "vmap lowering" item; pinned by jaxpr/HLO inspection in
``tests/test_batched.py``).  Nested ``vmap`` folds outer batch axes into the
existing batch axis.

The batched executor here also carries the per-sample **pre-kronization**
stage (vmapped ``jnp.kron`` + one batched sliced multiply), so
``make_batched_plan(shared_factors=False, enable_prekron=True)`` plans are
executable end to end — forward and backward.

Plan memoization is bounded: ops own their resolved plans/functions, and the
shim path shares small ``lru_cache``s (``kron_op_for``) instead of the old
unbounded ``maxsize=None`` memos.  Layer map: docs/architecture.md; public
surface: docs/api.md.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

from ..kernels import emit, hardware, ops
from ..runtime import chaos, guard, telemetry
from . import autotune
from .autotune import KronPlan, Stage, TileConfig
from .kron import KronProblem


# ---------------------------------------------------------------------------
# Plan lowering (KronPlan -> StageProgram, memoized) + program execution
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=512)
def _lowered(
    plan: KronPlan, ps: tuple[int, ...], qs: tuple[int, ...], batched: bool
) -> emit.StageProgram:
    """The op spine's bounded lowering memo: one StageProgram per (plan,
    signature, batchedness).  The backward program is NOT cached separately —
    it is ``emit.transpose`` of this one, derived mechanically."""
    return autotune.lower(plan, ps, qs, batched=batched)


def _signature(factors: Sequence[jax.Array]) -> tuple[tuple[int, ...], tuple[int, ...]]:
    off = 1 if factors[0].ndim == 3 else 0
    return (
        tuple(int(f.shape[off]) for f in factors),
        tuple(int(f.shape[off + 1]) for f in factors),
    )


# ---------------------------------------------------------------------------
# VJP building blocks (batch-polymorphic: one set for single AND batched)
# ---------------------------------------------------------------------------


def _sliced_vjp_factor(u: jax.Array, g: jax.Array, p: int, q: int) -> jax.Array:
    """df[p,q] = sum_{m,s} u[m, s*P+p] g[m, q*S+s]; per-sample ``(B, P, Q)``
    grads when ``u``/``g`` carry a leading batch axis."""
    s = int(u.shape[-1]) // p
    acc = jnp.promote_types(g.dtype, jnp.float32)
    if u.ndim == 2:
        u3 = u.reshape(u.shape[0], s, p)
        g3 = g.reshape(g.shape[0], q, s)
        return jnp.einsum("msp,mqs->pq", u3.astype(acc), g3.astype(acc))
    b, m = u.shape[0], u.shape[1]
    u4 = u.reshape(b, m, s, p)
    g4 = g.reshape(b, m, q, s)
    return jnp.einsum("bmsp,bmqs->bpq", u4.astype(acc), g4.astype(acc))


def _prekron_vjp(dK: jax.Array, stage_factors: Sequence[jax.Array]) -> tuple:
    """Split the cotangent of kron(rev[i+1], ..., rev[i]) back into per-factor
    cotangents, in ``stage_factors`` (application) order; vmapped over the
    leading batch axis for per-sample 3-D factors."""
    stage_factors = tuple(stage_factors)
    if dK.ndim == 3:
        return jax.vmap(lambda dk, fs: _prekron_vjp(dk, fs))(dK, stage_factors)
    if len(stage_factors) == 1:
        return (dK,)
    a = stage_factors[0]
    b = emit.prekron_product(stage_factors[1:])
    pa, qa = int(a.shape[0]), int(a.shape[1])
    pb, qb = int(b.shape[0]), int(b.shape[1])
    acc = jnp.promote_types(dK.dtype, jnp.float32)
    dk4 = dK.reshape(pb, pa, qb, qa).astype(acc)
    da = jnp.einsum("bpcq,bc->pq", dk4, b.astype(acc))
    db = jnp.einsum("bpcq,pq->bc", dk4, a.astype(acc))
    return (da,) + _prekron_vjp(db, stage_factors[1:])


def _conservative_batched_tiles(m: int, k: int, p: int, q: int) -> tuple[int, int]:
    """(t_m, t_k) for a single-factor batched call at t_b=1 that provably fits
    the kernel's VMEM budget — the fallback path must never itself raise."""
    t_m = min(8, m)
    while m % t_m:
        t_m -= 1
    growth = max(1.0, q / p)
    s = k // p
    t_s = max(
        d for d in range(1, s + 1)
        if s % d == 0 and t_m * d * p * growth <= emit.VMEM_BUDGET_ELEMS
    )
    return t_m, t_s * p


def _sliced_batched(y, f, backend):
    """One sliced multiply through the emitter, batch-polymorphic: 2-D
    operands run the per-factor sliced kernel; 3-D per-sample operands run a
    batched chain-of-one instruction tiled so Pallas always fits VMEM."""
    if f.ndim == 2:
        return ops.sliced_multiply(y, f, backend=backend)
    t_m, t_k = _conservative_batched_tiles(
        int(y.shape[1]), int(y.shape[2]), int(f.shape[1]), int(f.shape[2])
    )
    instr = emit.StageInstr(
        kind=emit.MULTIPLY, ps=(int(f.shape[1]),), qs=(int(f.shape[2]),),
        t_m=t_m, t_k=t_k, t_b=1,
    )
    return emit.run_stage(y, (f,), instr, backend=backend)


def _sliced_t_batched(g, f, backend):
    """Transposed twin of ``_sliced_batched`` (the input has Q-sized slices,
    dX has P-sized ones)."""
    if f.ndim == 2:
        return ops.sliced_multiply_t(g, f, backend=backend)
    p, q = int(f.shape[1]), int(f.shape[2])
    t_m, t_k = _conservative_batched_tiles(
        int(g.shape[1]), int(g.shape[2]) // q * p, p, q
    )
    instr = emit.StageInstr(
        kind=emit.TRANSPOSED_MULTIPLY, ps=(p,), qs=(q,), t_m=t_m, t_k=t_k, t_b=1
    )
    return emit.run_stage(g, (f,), instr, backend=backend)


def _stage_bwd_per_factor(u, g, stage_factors, backend):
    """Stage backward as per-factor planned ops — the fallback when the
    one-kernel fused backward cannot hold the stage's growth in VMEM (e.g.
    Q-tiled stages: the forward tiles Q, but the backward needs every
    factor-gradient pair).  Batch-polymorphic: the same loop serves single
    2-D stages and per-sample 3-D ones through the deduped emit bodies."""
    inputs = [u]
    for f in stage_factors[:-1]:
        inputs.append(_sliced_batched(inputs[-1], f, backend))
    dfs = [None] * len(stage_factors)
    for idx in reversed(range(len(stage_factors))):
        f = stage_factors[idx]
        p, q = int(f.shape[-2]), int(f.shape[-1])
        dfs[idx] = _sliced_vjp_factor(inputs[idx], g, p, q)
        g = _sliced_t_batched(g, f, backend)
    return g, tuple(dfs)


# ---------------------------------------------------------------------------
# Program-driven backward (ONE implementation for single and batched)
# ---------------------------------------------------------------------------


def _program_bwd(plan: KronPlan, backend: str, x, factors, g, f_pert: bool,
                 batched: bool):
    """Execute the backward of a lowered plan: (dx, dfs_by_rev_id or None).

    The dx chain is ``emit.transpose`` of the forward program — derived, not
    hand-mirrored; batched vs single is carried entirely by the program's
    ``t_b`` and the operands' rank.  Stage inputs are rematerialized with the
    FORWARD program (under jit XLA CSEs them against the primal chain, so the
    remat is effectively free at stage granularity).  When factor grads are
    needed, each transposed instruction is replaced by the one-kernel stage
    backward (``emit.run_stage_grad``), falling back to per-factor planned
    ops when the stage's live set cannot fit VMEM.
    """
    ps, qs = _signature(factors)
    prog = _lowered(plan, ps, qs, batched)
    rev = tuple(reversed(factors))
    stage_factors = [tuple(rev[i] for i in ins.factor_ids) for ins in prog.instrs]
    stage_inputs = []
    y = x
    for idx, (ins, sf) in enumerate(zip(prog.instrs, stage_factors)):
        stage_inputs.append(y)
        if idx + 1 < len(prog.instrs):
            y = emit.run_stage(y, sf, ins, backend=backend)
    bwd_prog = emit.transpose(prog)
    n_st = len(prog.instrs)
    dfs_by_id: dict[int, jax.Array] = {}
    for pos, t_ins in enumerate(bwd_prog.instrs):
        fwd_idx = n_st - 1 - pos
        f_ins = prog.instrs[fwd_idx]
        sf = stage_factors[fwd_idx]
        u = stage_inputs[fwd_idx]
        if f_ins.kind == emit.PREKRON:
            fk = emit.prekron_product(sf)
            pk_ins = dataclasses.replace(
                f_ins, kind=emit.MULTIPLY, ps=(int(fk.shape[-2]),),
                qs=(int(fk.shape[-1]),),
                t_qs=f_ins.t_qs if f_ins.t_qs and len(f_ins.t_qs) == 1 else None,
            )
            if f_pert:
                try:
                    g, (dk,) = emit.run_stage_grad(
                        u, g, (fk,), dataclasses.replace(pk_ins, t_m=t_ins.t_m),
                        backend=backend,
                    )
                except guard.KronError as e:
                    guard.record_event("bwd_per_factor", e)
                    g, (dk,) = _stage_bwd_per_factor(u, g, (fk,), backend)
                for fid, d in zip(f_ins.factor_ids, _prekron_vjp(dk, sf)):
                    dfs_by_id[fid] = d
            else:
                try:
                    g = emit.run_stage(g, (fk,), pk_ins.transpose(), backend=backend)
                except guard.KronError as e:
                    guard.record_event("bwd_per_factor", e)
                    g = _sliced_t_batched(g, fk, backend)
        elif f_pert:
            try:
                # Grad instr: the forward stage shape with the transposed
                # instruction's tuned M-tile (plan.bwd_stages via transpose()).
                g, dfs = emit.run_stage_grad(
                    u, g, sf, dataclasses.replace(f_ins, t_m=t_ins.t_m),
                    backend=backend,
                )
            except guard.KronError as e:
                # Fused backward tile exceeds VMEM (Q-tiled forward stages
                # have no Q relief on the gradient-pair side) — run the
                # stage per factor, still through planned dispatch.
                guard.record_event("bwd_per_factor", e)
                g, dfs = _stage_bwd_per_factor(u, g, sf, backend)
            for fid, d in zip(f_ins.factor_ids, dfs):
                dfs_by_id[fid] = d
        else:
            try:
                g = emit.run_stage(g, sf, t_ins, backend=backend)
            except guard.KronError as e:
                # The planner validated tiles against FORWARD block sizes;
                # the transposed shapes can overflow — walk the stage per
                # factor with fitted tiles instead.
                guard.record_event("bwd_per_factor", e)
                for f in reversed(sf):
                    g = _sliced_t_batched(g, f, backend)
    return g, (dfs_by_id if f_pert else None)


def _unfused_batched_plan(n: int, m: int) -> KronPlan:
    """plan=None semantics for the per-sample path: one batched sliced
    multiply per factor (the paper-faithful loop, batch-dispatched)."""
    t_m = min(m, 8)
    while m % t_m:
        t_m -= 1
    return KronPlan(
        tuple(Stage((i,), False, TileConfig(t_m, 1, 1)) for i in range(n))
    )


# ---------------------------------------------------------------------------
# Plan resolution (bounded memoization replacing the old unbounded memos)
# ---------------------------------------------------------------------------

_PLAN_MEMO_SIZE = 128


def _auto_prekron() -> bool:
    # pre-kronization trades FLOPs for MXU contraction depth — a win on the
    # 128x128 systolic array, measured a LOSS on CPU AVX (EXPERIMENTS.md
    # §Perf); auto-plans enable it only on TPU.  Applies to both the single
    # path and (now that the batched executor has a per-sample explicit-kron
    # stage) the per-sample batched path.
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=_PLAN_MEMO_SIZE)
def _resolve_plan(
    m: int,
    ps: tuple[int, ...],
    qs: tuple[int, ...],
    dtype_bytes: int,
    backend: str,
    enable_prekron: bool,
    tune: str,
    cache_path: str | None,
) -> KronPlan:
    with telemetry.span("plan", m=m, ps=ps, qs=qs, tune=tune):
        return autotune.make_plan(
            KronProblem(m, ps, qs),
            dtype_bytes=dtype_bytes,
            enable_prekron=enable_prekron,
            tune=tune,
            backend=backend,
            cache_path=cache_path,
        )


@functools.lru_cache(maxsize=_PLAN_MEMO_SIZE)
def _resolve_batched_plan(
    batch: int,
    m: int,
    ps: tuple[int, ...],
    qs: tuple[int, ...],
    dtype_bytes: int,
    backend: str,
    enable_prekron: bool,
    tune: str,
    cache_path: str | None,
    g_k: int,
) -> KronPlan:
    with telemetry.span("plan", m=m, ps=ps, qs=qs, tune=tune, batch=batch):
        return autotune.make_batched_plan(
            KronProblem(m, ps, qs),
            batch,
            shared_factors=False,
            dtype_bytes=dtype_bytes,
            enable_prekron=enable_prekron,
            tune=tune,
            backend=backend,
            cache_path=cache_path,
            g_k=g_k,
        )


class _PlanCtx(NamedTuple):
    """Static re-planning context carried on the primitives so batching rules
    can resolve the right plan for the transformed problem."""

    auto: bool  # plan came from the planner (re-plan on reshape) vs explicit
    tune: str
    cache_path: str | None
    prekron: bool


# ---------------------------------------------------------------------------
# The primitives: kron_matmul_p / kron_matmul_batched_p
# ---------------------------------------------------------------------------

kron_matmul_p = Primitive("kron_matmul")
kron_matmul_batched_p = Primitive("kron_matmul_batched")


def _fwd_ladder(x, factors, plan, backend, batched):
    """The per-op forward degradation ladder (docs/robustness.md):

      rung 0  planned     the lowered StageProgram (fused pallas chain / tuned
                          XLA scan — whatever the plan says)
      rung 1  per-factor  one conservatively-tiled sliced multiply per factor
      rung 2  xla-scan    the whole chain through the lax.scan executor

    Run under ``guard.run_ladder``: a typed failure degrades THE CALL with a
    once-per-process warning; ``patience`` consecutive degraded calls pin the
    op's signature to the surviving rung.  All rungs compute the identical
    contraction (tiles never split the reduction dim), so degradation is
    numerically invisible — the bitwise-parity property pinned by
    tests/test_guard.py.  Health is trace-time state: under jit the rung is
    chosen when the call is traced.

    Only CAPACITY failures degrade (VMEM overflow, illegal lowering): a
    ``NumericsError`` means the DATA is bad — every rung would compute the
    same non-finite values, so it propagates immediately instead of paying
    for three doomed attempts.
    """
    ps, qs = _signature(factors)
    prog = _lowered(plan, ps, qs, batched)
    rev = tuple(reversed(factors))
    key = ("kron", ps, qs, backend, batched)

    def _planned():
        return emit.run_program(x, factors, prog, backend=backend)

    def _per_factor():
        chaos.maybe_fail("per_factor")
        y = x
        for f in rev:
            y = _sliced_batched(y, f, backend)
        return guard.check_finite(y, "per_factor")

    def _xla_scan():
        y = emit._chain_xla(x, rev, t_b=1 if batched else None)
        return guard.check_finite(y, "xla_scan")

    return guard.run_ladder(
        key,
        (
            ("planned", _planned),
            ("per-factor", _per_factor),
            ("xla-scan", _xla_scan),
        ),
        catch=(guard.VmemOverflowError, guard.LoweringError),
    )


def _kron_impl(x, *factors, plan, backend, pctx):
    if plan is None:
        # Paper-faithful unfused loop (the C1 baseline): application order is
        # last factor first (Algorithm 1).
        y = x
        for f in reversed(factors):
            y = ops.sliced_multiply(y, f, backend=backend)
        return y
    return _fwd_ladder(x, factors, plan, backend, batched=False)


def _kron_abstract(x, *factors, plan, backend, pctx):
    k_out = math.prod(int(f.shape[1]) for f in factors)
    return jax.core.ShapedArray((x.shape[0], k_out), x.dtype)


def _kron_batched_impl(x, *factors, plan, backend, pctx):
    return _fwd_ladder(x, factors, plan, backend, batched=True)


def _kron_batched_abstract(x, *factors, plan, backend, pctx):
    k_out = math.prod(int(f.shape[2]) for f in factors)
    return jax.core.ShapedArray((x.shape[0], x.shape[1], k_out), x.dtype)


kron_matmul_p.def_impl(_kron_impl)
kron_matmul_p.def_abstract_eval(_kron_abstract)
mlir.register_lowering(
    kron_matmul_p, mlir.lower_fun(_kron_impl, multiple_results=False)
)
kron_matmul_batched_p.def_impl(_kron_batched_impl)
kron_matmul_batched_p.def_abstract_eval(_kron_batched_abstract)
mlir.register_lowering(
    kron_matmul_batched_p, mlir.lower_fun(_kron_batched_impl, multiple_results=False)
)


def _front(a, d, size):
    """Move the mapped axis to the front, or broadcast an unmapped operand."""
    if d is batching.not_mapped:
        return jnp.broadcast_to(a[None], (size, *a.shape))
    return jnp.moveaxis(a, d, 0)


def _axis_size(args, dims) -> int:
    for a, d in zip(args, dims):
        if d is not batching.not_mapped:
            return int(a.shape[d])
    raise ValueError("no mapped operand")  # unreachable under vmap


def _kron_batch_rule(args, dims, *, plan, backend, pctx):
    """vmap(kron_matmul): the ROADMAP's custom batching rule.

    * only ``x`` mapped (shared factors): the batch is a pure row-parallel
      axis, so it COLLAPSES into M and the single-problem planned path runs
      on the (B*M, K) rows — re-planned for the collapsed row count when the
      plan was auto-resolved.
    * any factor mapped (per-sample factors): route to the batch-grid
      kernels via ``kron_matmul_batched_p`` under a batched plan, instead of
      the generic per-op batching fallback.
    """
    b = _axis_size(args, dims)
    x, factors = args[0], args[1:]
    xd, fds = dims[0], tuple(dims[1:])
    ps = tuple(int(f.shape[-2]) for f in factors)
    qs = tuple(int(f.shape[-1]) for f in factors)
    if all(d is batching.not_mapped for d in fds):
        xb = _front(x, xd, b)
        m = int(xb.shape[1])
        p2 = plan
        if pctx.auto and plan is not None:
            p2 = _resolve_plan(
                b * m, ps, qs, x.dtype.itemsize, backend, pctx.prekron,
                pctx.tune, pctx.cache_path,
            )
        y = kron_matmul_p.bind(
            xb.reshape(b * m, -1), *factors, plan=p2, backend=backend, pctx=pctx
        )
        return y.reshape(b, m, -1), 0
    xb = _front(x, xd, b)
    fbs = tuple(_front(f, d, b) for f, d in zip(factors, fds))
    m = int(xb.shape[1])
    if plan is None:
        p2 = _unfused_batched_plan(len(factors), m)
    elif pctx.auto:
        p2 = _resolve_batched_plan(
            b, m, ps, qs, x.dtype.itemsize, backend, _auto_prekron(),
            pctx.tune, pctx.cache_path, 1,
        )
    else:
        p2 = plan
    y = kron_matmul_batched_p.bind(xb, *fbs, plan=p2, backend=backend, pctx=pctx)
    return y, 0


def _kron_batched_batch_rule(args, dims, *, plan, backend, pctx):
    """Nested vmap: fold the new batch axis into the existing one (C problems
    of B samples == one batch of C*B samples) and re-bind."""
    c = _axis_size(args, dims)
    x, factors = args[0], args[1:]
    xb = _front(x, dims[0], c)  # (C, B, M, K)
    fbs = tuple(_front(f, d, c) for f, d in zip(factors, dims[1:]))
    b = int(xb.shape[1])
    m = int(xb.shape[2])
    ps = tuple(int(f.shape[-2]) for f in fbs)
    qs = tuple(int(f.shape[-1]) for f in fbs)
    if pctx.auto:
        p2 = _resolve_batched_plan(
            c * b, m, ps, qs, x.dtype.itemsize, backend, _auto_prekron(),
            pctx.tune, pctx.cache_path, 1,
        )
    else:
        p2 = plan
    y = kron_matmul_batched_p.bind(
        xb.reshape(c * b, m, -1),
        *(f.reshape(c * b, *f.shape[2:]) for f in fbs),
        plan=p2, backend=backend, pctx=pctx,
    )
    return y.reshape(c, b, m, -1), 0


batching.primitive_batchers[kron_matmul_p] = _kron_batch_rule
batching.primitive_batchers[kron_matmul_batched_p] = _kron_batched_batch_rule


# ---------------------------------------------------------------------------
# Custom-VJP closures (op-owned; shared through small bounded caches)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _kron_fn(plan: KronPlan | None, backend: str, pctx: _PlanCtx, batched: bool):
    """THE custom-vjp closure: one factory for both execution modes.

    ``batched=False``: (x (M, K), 2-D factors_tuple); ``batched=True``:
    (x (B, M, K), per-sample 3-D factors).  The forward binds the matching
    primitive; the backward is the program-driven ``_program_bwd`` either
    way — batchedness lives in the lowered program's ``t_b`` and the operand
    ranks, not in a second code path.
    """
    prim = kron_matmul_batched_p if batched else kron_matmul_p

    def fwd_only(x, factors):
        return prim.bind(x, *factors, plan=plan, backend=backend, pctx=pctx)

    @jax.custom_vjp
    def kron_fn(x, factors):
        return fwd_only(x, factors)

    def kron_fwd(x_p, factors_p):
        x = x_p.value
        factors = tuple(f.value for f in factors_p)
        # Residuals: just (x, factors) plus static perturbation flags.  The
        # per-factor intermediates are recomputed in bwd (rematerialization):
        # storing them would cost ~N*M*K extra memory, while recompute adds
        # <= 1x forward FLOPs and is CSE'd against the primal under jit.
        f_pert = any(bool(f.perturbed) for f in factors_p)
        return fwd_only(x, factors), (x, factors, f_pert)

    def kron_bwd(res, g):
        with telemetry.scope("op_bwd"):
            return _bwd(res, g)

    def _bwd(res, g):
        x, factors, f_pert = res
        if isinstance(g, jax.custom_derivatives.SymbolicZero):
            return jnp.zeros_like(x), tuple(jnp.zeros_like(f) for f in factors)
        if plan is None and not batched:
            # Paper-faithful unfused loop (the C1 baseline's backward): one
            # transposed sliced multiply + factor contraction per factor.
            rev = tuple(reversed(factors))
            inputs = []
            y = x
            for i, f in enumerate(rev):
                inputs.append(y)
                if i + 1 < len(rev):
                    y = ops.sliced_multiply(y, f, backend="xla")
            dfs_rev = []
            for i in reversed(range(len(rev))):  # last applied stage first
                f = rev[i]
                p, q = int(f.shape[0]), int(f.shape[1])
                dfs_rev.append(_sliced_vjp_factor(inputs[i], g, p, q).astype(f.dtype))
                g = ops.sliced_multiply_t(g, f, backend=backend)
            dfactors = tuple(dfs_rev)  # appended rev[n-1]..rev[0] == F^1..F^N
            return g, dfactors
        dx, dfs_by_id = _program_bwd(plan, backend, x, factors, g, f_pert, batched)
        nf = len(factors)
        if dfs_by_id is None:
            dfactors = tuple(jnp.zeros_like(f) for f in factors)
        else:
            dfactors = tuple(
                dfs_by_id[nf - 1 - j].astype(factors[j].dtype) for j in range(nf)
            )
        return dx.astype(x.dtype), dfactors

    kron_fn.defvjp(kron_fwd, kron_bwd, symbolic_zeros=True)
    return kron_fn


# ---------------------------------------------------------------------------
# KronOp
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KronCost:
    """Analytic per-call cost of a KronOp (``KronOp.cost()``).

    The last three fields describe the slab-pipelined round schedule: with
    ``n_slabs > 1`` each round's all_to_all is split into per-row-slab
    collectives issued under the NEXT slab's chain compute, so of the
    ``comm_elems_per_device`` total only the exposed remainder sits on the
    critical path.  ``comm_hidden_elems`` is the analytic upper bound on the
    hidden share (``distributed.comm_hidden_elems``) and
    ``critical_path_s`` the resulting per-call wall-clock estimate —
    compute at the dtype's peak plus the EXPOSED transfer at the chip's ``ici_bw``
    plus one launch latency per collective.  Defaults keep local ops (and
    serial mesh schedules) at the historical ``KronCost(flops, comm,
    rounds)`` shape: nothing hidden, one collective per round.
    """

    flops: int
    comm_elems_per_device: int  # all_to_all payload; 0 for local ops
    rounds: int  # collective rounds; 0 for local ops
    comm_hidden_elems: int = 0  # payload hidden under slab-pipelined compute
    n_slabs: int = 1  # resolved slab count of the round schedule
    critical_path_s: float = 0.0  # analytic wall-clock (0.0 for local ops)


def _stage_flops_bytes(
    y_shape: Sequence[int], instr: emit.StageInstr, dtype_bytes: int
) -> tuple[int, int]:
    """Analytic (flops, hbm_bytes) of one stage launch on input ``y_shape``.

    Flops follow the sliced-multiply count (KronProblem.flops, per chained
    factor); bytes are the input + output intermediates plus the factor
    panels — the same two quantities the planner's analytic model trades off,
    so ``profile()`` drift is measured against the model that CHOSE the plan.
    """
    rows = math.prod(int(d) for d in y_shape[:-1]) or 1
    k = int(y_shape[-1])
    flops = 0
    factor_elems = 0
    if instr.kind == emit.PREKRON:
        pairs = [(instr.pprod, instr.qprod)]
        factor_elems = sum(p * q for p, q in zip(instr.ps, instr.qs))
    else:
        pairs = list(zip(instr.ps, instr.qs))
        factor_elems = sum(p * q for p, q in pairs)
    cur = k
    for p, q in pairs:
        out = (cur // p) * q
        flops += 2 * rows * out * p
        cur = out
    bytes_ = (rows * k + rows * cur + factor_elems) * dtype_bytes
    return flops, bytes_


def _stage_drift(
    measured: Sequence[float], predicted: Sequence[float], threshold: float
) -> list[bool]:
    """Per-stage cost-model drift flags for ``KronOp.profile()``.

    Absolute measured/predicted ratios are hardware-calibration, not drift —
    the model's PEAK/BW constants are TPU numbers and the host may be
    anything.  What the model does promise is the SPLIT of time across
    stages, so each stage's ratio is normalised by the whole-program ratio
    and flagged when it deviates by more than ``threshold``x either way.
    """
    total_m = sum(measured)
    total_p = sum(predicted)
    if total_m <= 0 or total_p <= 0 or threshold <= 0:
        return [False] * len(list(measured))
    overall = total_m / total_p
    flags = []
    for m_i, p_i in zip(measured, predicted):
        if p_i <= 0:
            flags.append(m_i > 0)
            continue
        drift = (m_i / p_i) / overall
        flags.append(drift > threshold or drift < 1.0 / threshold)
    return flags


_OP_STATE_SIZE = 8  # per-op (rows, dtype) -> plan/fn entries kept


def signature_of(
    factors: Sequence[jax.Array], shared_factors: bool
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(ps, qs) of a factor list, validating the ndim for the sharing mode."""
    factors = tuple(factors)
    if not factors:
        raise ValueError("need at least one factor")
    if shared_factors:
        if any(f.ndim != 2 for f in factors):
            raise ValueError("shared_factors=True expects 2-D (P_i, Q_i) factors")
        return (
            tuple(int(f.shape[0]) for f in factors),
            tuple(int(f.shape[1]) for f in factors),
        )
    if any(f.ndim != 3 for f in factors):
        raise ValueError("shared_factors=False expects 3-D (B, P_i, Q_i) factors")
    return (
        tuple(int(f.shape[1]) for f in factors),
        tuple(int(f.shape[2]) for f in factors),
    )


class KronOp:
    """A Kron-Matmul problem resolved into an executable operator.

    ``KronOp(ps, qs)`` describes ``x @ (F^1 (x) ... (x) F^N)`` with factor
    shapes ``F^i: (P_i, Q_i)``; calling the op executes it with the plan
    (and, on a mesh, the round schedule) resolved ONCE and owned by the op —
    repeated calls never re-enter plan memo lookups, and two ops with the
    same signature share one plan object through a bounded module cache.

    Parameters
    ----------
    ps, qs : factor row/column dims, problem order.
    m : optional row count the plan is resolved for at construction.  When
        omitted, plans resolve lazily on first call per distinct row count
        (kept in a small op-owned table) and ``.plan`` defaults to the
        paper's M=16 CG-block row count.
    batch : B for the batched execution modes; None = single-problem.
    shared_factors : with ``batch``: one 2-D factor set for every sample
        (B collapses into the row axis) vs per-sample 3-D ``(B, P_i, Q_i)``
        factors (the batch-grid kernels).
    mesh : a ``(data, model)`` jax Mesh — execution becomes the paper §5
        distributed rounds; the round schedule is validated at construction
        (raises ``ValueError`` when no legal relocation schedule exists).
    backend / plan / tune / cache_path : as in the legacy entry points;
        ``plan`` may be ``"auto"``, ``None`` (paper-faithful unfused loop),
        or an explicit ``KronPlan``.
    n_slabs : row-slab count for the mesh round pipeline.  ``"auto"`` lets
        the planner decide (per-sample batched plans carry it as
        ``KronPlan.n_slabs``; the shared/single path asks
        ``autotune.choose_n_slabs``); an explicit int forces the schedule,
        clamped to a divisor of the local row axis.  Ignored off-mesh.

    The dispatch spine is two orthogonal axes — (local | mesh) x (single |
    batched) — and every legacy ``kron_matmul*`` entry point is a shim over
    it.  ``vmap`` over a KronOp-backed call routes through the custom
    batching rules on the op's primitives (see module docstring).
    """

    def __init__(
        self,
        ps: Sequence[int],
        qs: Sequence[int],
        *,
        m: int | None = None,
        batch: int | None = None,
        shared_factors: bool = True,
        mesh=None,
        data_axis: str | tuple[str, ...] = "data",
        model_axis: str = "model",
        per_iteration: bool = False,
        backend: str = "auto",
        plan: KronPlan | str | None = "auto",
        tune: str = "analytic",
        cache_path: str | None = None,
        dtype_bytes: int = 4,
        enable_prekron: bool | None = None,
        n_slabs: int | str = "auto",
    ):
        self.ps = tuple(int(p) for p in ps)
        self.qs = tuple(int(q) for q in qs)
        if len(self.ps) != len(self.qs) or not self.ps:
            raise ValueError(f"ps/qs must be equal-length and non-empty: {ps}, {qs}")
        if any(d <= 0 for d in self.ps + self.qs):
            raise ValueError(f"factor dims must be positive: {ps}, {qs}")
        if batch is not None and batch <= 0:
            raise ValueError(f"batch must be positive, got {batch}")
        if isinstance(plan, str) and plan != "auto":
            raise ValueError(f"plan must be 'auto', None, or a KronPlan: {plan!r}")
        if isinstance(n_slabs, str):
            if n_slabs != "auto":
                raise ValueError(f"n_slabs must be 'auto' or an int: {n_slabs!r}")
        elif int(n_slabs) <= 0:
            raise ValueError(f"n_slabs must be positive, got {n_slabs}")
        self.n = len(self.ps)
        self.k = math.prod(self.ps)
        self.k_out = math.prod(self.qs)
        self.batch = batch
        self.shared_factors = bool(shared_factors)
        self.backend = backend
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.per_iteration = bool(per_iteration)
        # "auto" defers the slab count to the planner (per-sample batched
        # plans own it as KronPlan.n_slabs; the shared/single round path asks
        # autotune.choose_n_slabs); an int forces it (clamped to a divisor of
        # the local row axis by the executor).  Meaningless off-mesh.
        self._n_slabs_arg = n_slabs if n_slabs == "auto" else int(n_slabs)
        self._m = m
        self._dtype_bytes = dtype_bytes
        self._plan_arg = plan
        # ``enable_prekron=None`` keeps the backend auto-gate (TPU on, else
        # off); an explicit bool overrides it — e.g. the optimizer's
        # preconditioner apply must NEVER densify kron(L, R) per layer.
        self._enable_prekron = enable_prekron
        prekron = _auto_prekron() if enable_prekron is None else bool(enable_prekron)
        self._ctx = _PlanCtx(plan == "auto", tune, cache_path, prekron)
        if mesh is not None:
            from .distributed import _mesh_size, plan_rounds

            self.g_m = _mesh_size(mesh, data_axis)
            self.g_k = int(mesh.shape[model_axis])
            if self.k % self.g_k:
                raise ValueError(
                    f"K={self.k} not divisible by model axis G_K={self.g_k}"
                )
            # Round schedule resolved (and validated) at construction.
            self.rounds = tuple(
                plan_rounds(
                    self.k // self.g_k,
                    tuple(reversed(self.ps)),
                    tuple(reversed(self.qs)),
                    self.g_k,
                    minimal=self.per_iteration,
                )
            )
        else:
            self.g_m = self.g_k = 1
            self.rounds = None
        # Op-owned resolved state: (rows-or-(b,m), dtype_bytes) -> plan / fn.
        self._plans: dict = {}
        self._fns: dict = {}
        if m is not None and mesh is None:
            if batch is not None and not self.shared_factors:
                self._ensure_batched(batch, m, dtype_bytes)
            else:
                rows = m if batch is None else batch * m
                self._ensure_single(rows, dtype_bytes)

    # -- plan / fn resolution (op-owned, bounded) ---------------------------

    def _remember(self, cache: dict, key, value):
        cache[key] = value
        while len(cache) > _OP_STATE_SIZE:
            cache.pop(next(iter(cache)))
        return value

    def _single_plan(self, rows: int, dtype_bytes: int) -> KronPlan | None:
        if self._plan_arg == "auto":
            return _resolve_plan(
                rows, self.ps, self.qs, dtype_bytes, self.backend,
                self._ctx.prekron, self._ctx.tune, self._ctx.cache_path,
            )
        return self._plan_arg

    def _batched_plan(self, b: int, m: int, dtype_bytes: int) -> KronPlan:
        if self._plan_arg == "auto":
            if self.mesh is not None and self._ctx.tune == "measure":
                # The measured distributed tuner wall-clocks candidate
                # (t_b, n_slabs) schedules ON the mesh, so it needs the mesh
                # itself — bypass the hashable-args memo; the plan cache
                # (``;gk=`` key) deduplicates across ops instead.
                with telemetry.span(
                    "plan", m=m, ps=self.ps, qs=self.qs, tune="measure",
                    batch=b, g_k=self.g_k,
                ):
                    return autotune.make_batched_plan(
                        KronProblem(m, self.ps, self.qs), b,
                        shared_factors=False, dtype_bytes=dtype_bytes,
                        enable_prekron=self._ctx.prekron, tune="measure",
                        backend=self.backend,
                        cache_path=self._ctx.cache_path, g_k=self.g_k,
                        mesh=self.mesh, data_axis=self.data_axis,
                        model_axis=self.model_axis,
                    )
            return _resolve_batched_plan(
                b, m, self.ps, self.qs, dtype_bytes, self.backend,
                self._ctx.prekron, self._ctx.tune, self._ctx.cache_path,
                self.g_k,
            )
        if self._plan_arg is None:
            return _unfused_batched_plan(self.n, m)
        return self._plan_arg

    def _ensure_single(self, rows: int, dtype_bytes: int):
        key = ("single", rows, dtype_bytes)
        fn = self._fns.get(key)
        if fn is None:
            plan = self._single_plan(rows, dtype_bytes)
            self._remember(self._plans, key, plan)
            fn = self._remember(
                self._fns, key, _kron_fn(plan, self.backend, self._ctx, False)
            )
        return fn

    def _ensure_batched(self, b: int, m: int, dtype_bytes: int):
        key = ("batched", b, m, dtype_bytes)
        fn = self._fns.get(key)
        if fn is None:
            plan = self._batched_plan(b, m, dtype_bytes)
            self._remember(self._plans, key, plan)
            fn = self._remember(
                self._fns, key, _kron_fn(plan, self.backend, self._ctx, True)
            )
        return fn

    def _default_rows(self) -> int:
        # The paper's M=16 CG-block row count when no row hint exists.
        return self._m if self._m is not None else 16

    def _resolve_n_slabs(self, m_loc: int, plan: KronPlan | None = None) -> int:
        """Resolved slab count of the round schedule for ``m_loc`` local rows.

        Explicit ints are honoured (clamped to a divisor of the row axis —
        the same clamp the executor applies); ``"auto"`` reads the batched
        plan's ``n_slabs`` when one is supplied (the per-sample mesh path,
        where the planner traded slabs against ``t_b``) and otherwise asks
        the analytic model.  Always 1 without a model axis to overlap."""
        if self.mesh is None or self.g_k <= 1 or m_loc <= 1:
            return 1
        if self._n_slabs_arg != "auto":
            return emit.effective_slabs(m_loc, int(self._n_slabs_arg))
        if plan is not None:
            return emit.effective_slabs(m_loc, int(getattr(plan, "n_slabs", 1)))
        b = 1 if (self.batch is None or self.shared_factors) else self.batch
        n = autotune.choose_n_slabs(
            KronProblem(m_loc, self.ps, self.qs), self.g_k,
            batch=b, dtype_bytes=self._dtype_bytes,
        )
        return emit.effective_slabs(m_loc, n)

    @property
    def plan(self) -> KronPlan | None:
        """The op's resolved KronPlan (last resolved; resolves for the
        construction-time ``m`` or the M=16 default when none seen yet).

        Mesh ops on the single/shared path return None: that path executes
        the ROUND schedule (``self.rounds``), not a stage plan — resolving
        one here would report (and under tune="measure", measure) a plan
        that never runs.  Per-sample mesh ops do use a batched plan (its
        ``t_b`` tiles the round kernels), so they resolve normally."""
        if self.mesh is not None and (self.batch is None or self.shared_factors):
            return None
        if self._plans:
            return next(reversed(self._plans.values()))
        m = self._default_rows()
        if self.batch is not None and not self.shared_factors:
            return self._batched_plan(self.batch, m, self._dtype_bytes)
        rows = m if self.batch is None else self.batch * m
        return self._single_plan(rows, self._dtype_bytes)

    # -- derivations --------------------------------------------------------

    def _derive(self, **changes) -> "KronOp":
        kw = dict(
            m=self._m, batch=self.batch, shared_factors=self.shared_factors,
            mesh=self.mesh, data_axis=self.data_axis,
            model_axis=self.model_axis, per_iteration=self.per_iteration,
            backend=self.backend, plan=self._plan_arg, tune=self._ctx.tune,
            cache_path=self._ctx.cache_path, dtype_bytes=self._dtype_bytes,
            enable_prekron=self._enable_prekron, n_slabs=self._n_slabs_arg,
        )
        kw.update(changes)
        return KronOp(self.ps, self.qs, **kw)

    def with_mesh(
        self, mesh, *, data_axis="data", model_axis="model",
        per_iteration: bool = False,
    ) -> "KronOp":
        """The same problem executed as distributed rounds on ``mesh``."""
        return self._derive(
            mesh=mesh, data_axis=data_axis, model_axis=model_axis,
            per_iteration=per_iteration,
        )

    def with_batch(
        self, batch: int | None, *, shared_factors: bool | None = None
    ) -> "KronOp":
        """The same problem over ``batch`` independent samples.

        The row-count hint is dropped in the derivation: a single op's ``m``
        is TOTAL rows while a batched op's ``m`` is rows PER SAMPLE, so
        carrying it over would eagerly resolve a plan for the wrong shape.
        The derived op resolves lazily on its first call instead."""
        if shared_factors is None:
            shared_factors = self.shared_factors
        return self._derive(batch=batch, shared_factors=shared_factors, m=None)

    # -- size / cost queries -------------------------------------------------

    def out_shape(self, x_shape: Sequence[int]) -> tuple[int, ...]:
        """Output shape for an input of shape ``x_shape`` (the handle API's
        size query: allocate outputs without tracing)."""
        x_shape = tuple(int(d) for d in x_shape)
        if not x_shape or x_shape[-1] != self.k:
            raise ValueError(
                f"x last dim {x_shape[-1] if x_shape else None} != "
                f"prod(P)={self.k} for {self.ps}"
            )
        if self.batch is not None:
            if len(x_shape) < 2 or x_shape[0] != self.batch:
                raise ValueError(
                    f"batched op expects (B={self.batch}, ..., K), got {x_shape}"
                )
        return (*x_shape[:-1], self.k_out)

    def cost(self, m: int | None = None) -> KronCost:
        """Analytic cost of one call: sliced-multiply FLOPs plus, on a mesh,
        the all_to_all payload (elements per device, all rounds), the share
        of it the slab pipeline hides under compute, and the resulting
        critical-path wall-clock estimate (``KronCost`` docstring)."""
        m = m if m is not None else self._default_rows()
        b = self.batch or 1
        if self.batch is not None and not self.shared_factors:
            flops = b * KronProblem(m, self.ps, self.qs).flops
        else:
            flops = KronProblem(b * m, self.ps, self.qs).flops
        if self.mesh is None:
            return KronCost(flops, 0, 0)
        from .distributed import comm_elems_per_device, comm_hidden_elems

        rows = b * m if self.shared_factors else m
        m_loc = max(1, rows // self.g_m)
        comm_batch = 1 if self.shared_factors else b
        ps_rev = tuple(reversed(self.ps))
        qs_rev = tuple(reversed(self.qs))
        comm = comm_elems_per_device(
            m_loc, self.k // self.g_k, ps_rev, qs_rev, self.g_k,
            rounds=self.rounds, batch=comm_batch,
        )
        n = self._resolve_n_slabs(m_loc)
        hidden = comm_hidden_elems(
            m_loc, self.k // self.g_k, ps_rev, qs_rev, self.g_k,
            rounds=self.rounds, batch=comm_batch, n_slabs=n,
        )
        # Critical path: per-device compute at the dtype's peak, the EXPOSED
        # transfer at the chip's ici_bw, one launch latency per collective issued.
        hw = hardware.tpu_spec()
        peak = (
            hw.peak_flops_bf16 if self._dtype_bytes <= 2
            else hw.peak_flops_f32
        )
        critical = (
            flops / (self.g_m * self.g_k) / peak
            + (comm - hidden) * self._dtype_bytes / hw.ici_bw
            + len(self.rounds) * n * autotune.A2A_LATENCY_S
        )
        return KronCost(flops, comm, len(self.rounds), hidden, n, critical)

    def profile(
        self,
        x: jax.Array,
        factors: Sequence[jax.Array],
        *,
        warmup: int = 1,
        iters: int = 3,
        drift_threshold: float | None = None,
    ) -> dict:
        """Measure the lowered StageProgram stage by stage and compare the
        wall-clock split against the planner's analytic cost model.

        Each stage of the op's forward program is executed eagerly (the same
        ``emit.run_stage`` calls ``run_program`` chains) with
        ``jax.block_until_ready`` timing — min over ``iters`` runs after
        ``warmup`` discarded ones.  The analytic prediction per stage is the
        planner's own two-term model (flops/peak + bytes/bandwidth); a stage
        whose measured share deviates from its predicted share by more than
        ``drift_threshold`` (default ``telemetry.DRIFT_THRESHOLD``) in either
        direction is flagged as cost-model drift (see ``_stage_drift`` for
        why the SPLIT, not the absolute ratio, is the contract).

        Mesh ops profile their local-equivalent plan — per-stage timing
        inside a ``shard_map`` body is not observable from the host — and the
        report carries the analytic collective cost as predicted-only under
        ``"comm"``.  ``plan=None`` (paper-faithful unfused) ops have no
        StageProgram and raise ``PlanError``.

        When telemetry is active the report is stamped into the registry
        (``telemetry.mark_profile``) and each flagged stage emits a
        ``cost_model_drift`` event; with telemetry off the dict is simply
        returned.
        """
        factors = tuple(factors)
        self._check_factors(factors)
        threshold = (
            telemetry.DRIFT_THRESHOLD
            if drift_threshold is None
            else float(drift_threshold)
        )
        op = self._derive(mesh=None, m=None) if self.mesh is not None else self
        report = op._profile_stages(
            x, factors, warmup=int(warmup), iters=int(iters), threshold=threshold
        )
        if self.mesh is not None:
            cost = self.cost(report["signature"]["m"])
            hw = hardware.tpu_spec()
            report["signature"]["mesh"] = [self.g_m, self.g_k]
            report["comm"] = {
                "elems_per_device": cost.comm_elems_per_device,
                "rounds": cost.rounds,
                "n_slabs": cost.n_slabs,
                "hidden_elems": cost.comm_hidden_elems,
                "critical_path_s": cost.critical_path_s,
                "predicted_s": cost.comm_elems_per_device
                * self._dtype_bytes
                / hw.hbm_bw,
                "measured_s": None,  # rounds run inside shard_map bodies
            }
            # Reconcile the analytic overlap term against the per-slab
            # telemetry gauges (comm.round{k}.slab{s}.elems_per_device): the
            # registry's hidden total is per-round ``total - max(slab)``,
            # which equals the model's ``payload - payload/n`` when the
            # executor ran the schedule cost() predicted.
            tele = telemetry.comm_summary()
            if tele:
                observed_hidden = sum(r["hidden"] for r in tele.values())
                report["comm"]["telemetry_hidden_elems"] = observed_hidden
                report["comm"]["telemetry_rounds"] = tele
        telemetry.mark_profile(report)
        for i in report["drift_flagged"]:
            st = report["stages"][i]
            telemetry.event(
                "cost_model_drift",
                stage=i,
                drift=st["drift"],
                instr=st["instr"],
            )
        return report

    def _profile_stages(
        self, x: jax.Array, factors: tuple, *, warmup: int, iters: int,
        threshold: float,
    ) -> dict:
        dtype_bytes = x.dtype.itemsize
        if self.batch is not None and not self.shared_factors:
            b = self.batch
            m_rows = math.prod(int(d) for d in x.shape[1:-1]) or 1
            plan = self._batched_plan(b, m_rows, dtype_bytes)
            batched = True
            y = x.reshape(b, m_rows, self.k)
        else:
            rows = math.prod(int(d) for d in x.shape[:-1]) or 1
            plan = self._single_plan(rows, dtype_bytes)
            batched = False
            y = x.reshape(rows, self.k)
            m_rows = rows // (self.batch or 1)
        if plan is None:
            raise guard.PlanError(
                "profile() needs a planned op (plan='auto' or an explicit "
                "KronPlan): plan=None runs the paper-faithful unfused loop, "
                "which has no StageProgram to time stage by stage"
            )
        prog = _lowered(plan, self.ps, self.qs, batched)
        rev = tuple(reversed(factors))
        hw = hardware.tpu_spec()
        peak = (
            hw.peak_flops_bf16 if dtype_bytes <= 2 else hw.peak_flops_f32
        )
        stages: list[dict] = []
        measured: list[float] = []
        predicted: list[float] = []
        with telemetry.span("profile", ps=self.ps, qs=self.qs):
            for idx, instr in enumerate(prog.instrs):
                sf = tuple(rev[i] for i in instr.factor_ids)
                y_in = y

                def run(y_in=y_in, sf=sf, instr=instr):
                    return emit.run_stage(y_in, sf, instr, backend=self.backend)

                for _ in range(max(0, warmup)):
                    jax.block_until_ready(run())
                best = float("inf")
                out = None
                for _ in range(max(1, iters)):
                    t0 = time.perf_counter()
                    out = run()
                    jax.block_until_ready(out)
                    best = min(best, time.perf_counter() - t0)
                flops, nbytes = _stage_flops_bytes(y.shape, instr, dtype_bytes)
                pred = flops / peak + nbytes / hw.hbm_bw
                measured.append(best)
                predicted.append(pred)
                stages.append(
                    {
                        "stage": idx,
                        "instr": instr.describe(),
                        "factor_ids": list(instr.factor_ids),
                        "measured_s": best,
                        "predicted_s": pred,
                        "flops": flops,
                        "bytes": nbytes,
                    }
                )
                y = out
        flags = _stage_drift(measured, predicted, threshold)
        total_m = sum(measured)
        total_p = sum(predicted)
        overall = total_m / total_p if total_p > 0 else float("nan")
        for st, m_i, p_i, flag in zip(stages, measured, predicted, flags):
            st["share_measured"] = m_i / total_m if total_m > 0 else 0.0
            st["share_predicted"] = p_i / total_p if total_p > 0 else 0.0
            st["drift"] = (
                (m_i / p_i) / overall
                if p_i > 0 and overall == overall
                else float("inf")
            )
            st["drift_flagged"] = flag
        cost = self.cost(m_rows)
        return {
            "signature": {
                "ps": list(self.ps),
                "qs": list(self.qs),
                "m": m_rows,
                "batch": self.batch,
                "backend": self.backend,
            },
            "plan": plan.describe(),
            "program": prog.describe(),
            "stages": stages,
            "measured_s": total_m,
            "predicted_s": total_p,
            "cost_flops": cost.flops,
            "measured_gflops_s": (
                cost.flops / total_m / 1e9 if total_m > 0 else 0.0
            ),
            "drift_threshold": threshold,
            "drift_flagged": [i for i, f in enumerate(flags) if f],
            "warmup": warmup,
            "iters": iters,
        }

    def stage_executors(
        self, m: int | None = None, dtype=jnp.float32
    ) -> list[tuple[str, str, str]]:
        """How each planned stage runs on the COMPILED Pallas backend for
        ``m`` rows (default: the op's row hint, else 16): ``pallas(t_b, t_m,
        t_k)`` with the legal tiles the emitter uses, or ``xla`` where no
        legal kernel tiling fits VMEM — for the forward kernel and for the
        stage-backward kernel.  K-tiled kernels add the view of their
        y-side array (``emit.stage_view``): ``:bitcast`` where it is the
        flat array's bytes, ``:relayout`` where XLA relayouts it.  Decided
        from shapes alone, never from a failed compile.  One ``(stage,
        forward, backward)`` triple per stage; empty for mesh ops without a
        stage plan."""
        m = self._default_rows() if m is None else int(m)
        itemsize = jnp.dtype(dtype).itemsize
        per_sample = self.batch is not None and not self.shared_factors
        if self.mesh is not None and not per_sample:
            return []
        if per_sample:
            plan = self._batched_plan(self.batch, m, itemsize)
            lead = (self.batch,)
        else:
            m = m if self.batch is None else self.batch * m
            plan = self._single_plan(m, itemsize)
            lead = ()
        if plan is None:
            return []

        def how(ins, shape, grad):
            tiles = emit.stage_tiles(ins, shape, dtype, grad=grad)
            if tiles is None:
                return "xla"
            view = emit.stage_view(ins, shape, dtype, grad=grad)
            return f"pallas{tiles}" + (f":{view}" if view else "")

        cols, out = self.k, []
        for ins in _lowered(plan, self.ps, self.qs, per_sample).instrs:
            shape = lead + (m, cols)
            out.append(
                (ins.describe(), how(ins, shape, False), how(ins, shape, True))
            )
            cols = cols // ins.pprod * ins.qprod
        return out

    def describe(self) -> str:
        mode = "batched" if self.batch is not None else "single"
        shared = "" if self.batch is None else (
            ", shared" if self.shared_factors else ", per-sample"
        )
        where = (
            f"mesh({self.g_m}x{self.g_k})" if self.mesh is not None else "local"
        )
        plan = self.plan
        if plan is not None:
            pdesc = plan.describe()
        elif self.rounds is not None:
            pdesc = f"rounds{list(self.rounds)}"  # mesh path: the schedule IS the plan
        else:
            pdesc = "unfused"
        base = (
            f"KronOp(ps={list(self.ps)}, qs={list(self.qs)}, {mode}"
            f"{shared}, {where}, backend={self.backend}) :: {pdesc}"
        )
        if emit.resolve_backend(self.backend) == "pallas" and plan is not None:
            # Which stages the compiled emitter routes to XLA (no legal
            # kernel tiling at the op's row hint), decided from shapes.
            execs = self.stage_executors()
            base += " :: exec[" + "; ".join(
                f"{fwd}/{grad}" for _, fwd, grad in execs
            ) + "]"
        return base + self._health_suffix() + self._telemetry_suffix()

    def _telemetry_suffix(self) -> str:
        """One-line KronScope state when telemetry is live — empty when off,
        so ``describe()`` stays byte-stable for untelemetered processes."""
        if not telemetry.active():
            return ""
        return " :: " + telemetry.summary_line()

    def _health_suffix(self) -> str:
        """Guard-layer health for this op's signature — empty while healthy,
        a `:: guard[...]` tail once any ladder keyed on (ps, qs) degraded."""
        parts = []
        for key, h in guard.health_entries():
            if (
                isinstance(key, tuple)
                and len(key) >= 3
                and key[1] == self.ps
                and key[2] == self.qs
                and (h.degraded_calls or h.pinned or h.errors)
            ):
                rung = f"rung={h.rung}{' pinned' if h.pinned else ''}"
                errs = ",".join(f"{k}x{v}" for k, v in sorted(h.errors.items()))
                parts.append(
                    f"{key[0]}: {rung} degraded={h.degraded_calls}/{h.calls}"
                    + (f" [{errs}]" if errs else "")
                )
        return f" :: guard[{'; '.join(parts)}]" if parts else ""

    def __repr__(self) -> str:
        return self.describe()

    # -- execution -----------------------------------------------------------

    def _check_factors(self, factors: tuple[jax.Array, ...]):
        shared = self.batch is None or self.shared_factors
        ps, qs = signature_of(factors, shared)
        if (ps, qs) != (self.ps, self.qs):
            raise ValueError(
                f"factor shapes {ps}x{qs} do not match op signature "
                f"{self.ps}x{self.qs}"
            )
        if not shared:
            for f in factors:
                if int(f.shape[0]) != self.batch:
                    raise ValueError(
                        f"factor batch {f.shape[0]} != x batch {self.batch}"
                    )

    def __call__(self, x: jax.Array, factors: Sequence[jax.Array]) -> jax.Array:
        # One scope for the whole forward call, local or mesh: the plan's
        # relayouts around the program, and its rounds, issue under it.
        with telemetry.scope("op"):
            return self._call(x, factors)

    def _call(self, x: jax.Array, factors: Sequence[jax.Array]) -> jax.Array:
        factors = tuple(factors)
        self._check_factors(factors)
        if self.batch is None:
            if x.shape[-1] != self.k:
                raise ValueError(
                    f"x last dim {x.shape[-1]} != prod(P)={self.k} for {self.ps}"
                )
            if self.mesh is not None:
                return self._run_mesh_single(x, factors)
            lead = x.shape[:-1]
            m = math.prod(lead) if lead else 1
            fn = self._ensure_single(m, x.dtype.itemsize)
            y = fn(x.reshape(m, self.k), factors)
            return y.reshape(*lead, self.k_out)
        # batched modes
        if x.ndim < 2:
            raise ValueError(
                f"x needs a leading batch axis: (B, ..., K), got {x.shape}"
            )
        if int(x.shape[0]) != self.batch:
            raise ValueError(f"x batch {x.shape[0]} != op batch {self.batch}")
        if x.shape[-1] != self.k:
            raise ValueError(
                f"x last dim {x.shape[-1]} != prod(P)={self.k} for {self.ps}"
            )
        b = self.batch
        lead = x.shape[1:-1]
        m = math.prod(lead) if lead else 1
        if self.shared_factors:
            # Collapse B into M and run the single-problem spine: both are
            # pure row indices of the same contiguous array.
            if self.mesh is not None:
                y = self._run_mesh_single(x.reshape(b * m, self.k), factors)
            else:
                fn = self._ensure_single(b * m, x.dtype.itemsize)
                y = fn(x.reshape(b * m, self.k), factors)
            return y.reshape(b, *lead, self.k_out)
        if self.mesh is not None:
            if x.ndim != 3:
                raise ValueError(f"x must be (B, M, K), got shape {x.shape}")
            return self._run_mesh_batched(x, factors)
        fn = self._ensure_batched(b, m, x.dtype.itemsize)
        y = fn(x.reshape(b, m, self.k), factors)
        return y.reshape(b, *lead, self.k_out)

    def _run_mesh_single(self, x, factors):
        from . import distributed

        if x.ndim != 2:
            raise ValueError(f"distributed op expects x (M, K), got {x.shape}")
        n_slabs = self._resolve_n_slabs(max(1, int(x.shape[0]) // self.g_m))

        def _mesh_slabbed():
            return distributed.run_distributed_rounds(
                x, factors, self.mesh,
                data_axis=self.data_axis, model_axis=self.model_axis,
                backend=self.backend, per_iteration=self.per_iteration,
                n_slabs=n_slabs,
            )

        def _mesh():
            return distributed.run_distributed_rounds(
                x, factors, self.mesh,
                data_axis=self.data_axis, model_axis=self.model_axis,
                backend=self.backend, per_iteration=self.per_iteration,
            )

        def _local():
            fn = self._ensure_single(int(x.shape[0]), x.dtype.itemsize)
            return fn(x, factors)

        # Mesh ladder: a failed slab relocation degrades to the serial round
        # schedule, a failed round to single-host execution on the
        # (replicated) operands — same contraction, no collectives.  Only
        # CollectiveError degrades; anything else is a bug.
        rungs = (("mesh-rounds", _mesh), ("local", _local))
        if n_slabs > 1:
            rungs = (("mesh-slabbed", _mesh_slabbed),) + rungs
        return guard.run_ladder(
            ("mesh", self.ps, self.qs, self.backend, "single"),
            rungs,
            catch=(guard.CollectiveError,),
        )

    def _run_mesh_batched(self, x, factors):
        from . import distributed

        b, m = int(x.shape[0]), int(x.shape[1])
        key = ("mesh-batched", b, m, x.dtype.itemsize)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._remember(
                self._plans, key,
                self._batched_plan(b, max(1, m // self.g_m), x.dtype.itemsize),
            )
        n_slabs = self._resolve_n_slabs(max(1, m // self.g_m), plan)

        def _mesh_slabbed():
            return distributed.run_batched_distributed_rounds(
                x, factors, self.mesh, t_b=plan.t_b,
                data_axis=self.data_axis, model_axis=self.model_axis,
                backend=self.backend, per_iteration=self.per_iteration,
                n_slabs=n_slabs,
            )

        def _mesh():
            return distributed.run_batched_distributed_rounds(
                x, factors, self.mesh, t_b=plan.t_b,
                data_axis=self.data_axis, model_axis=self.model_axis,
                backend=self.backend, per_iteration=self.per_iteration,
            )

        def _local():
            fn = self._ensure_batched(b, m, x.dtype.itemsize)
            return fn(x, factors)

        rungs = (("mesh-rounds", _mesh), ("local", _local))
        if n_slabs > 1:
            rungs = (("mesh-slabbed", _mesh_slabbed),) + rungs
        return guard.run_ladder(
            ("mesh", self.ps, self.qs, self.backend, "batched"),
            rungs,
            catch=(guard.CollectiveError,),
        )


# ---------------------------------------------------------------------------
# Bounded op factory (the shim path) + deprecation bookkeeping
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def kron_op_for(
    ps: tuple[int, ...],
    qs: tuple[int, ...],
    *,
    m: int | None = None,
    batch: int | None = None,
    shared_factors: bool = True,
    mesh=None,
    data_axis="data",
    model_axis: str = "model",
    per_iteration: bool = False,
    backend: str = "auto",
    plan: KronPlan | str | None = "auto",
    tune: str = "analytic",
    cache_path: str | None = None,
    dtype_bytes: int = 4,
    enable_prekron: bool | None = None,
    n_slabs: int | str = "auto",
) -> KronOp:
    """Shared, bounded ``KronOp`` factory: same signature -> same op object.

    This is the cache behind the legacy ``kron_matmul*`` shims and the
    consumers that key ops on runtime shapes (layers, GP kernels, serving).
    Plans themselves are additionally shared through the engine's bounded
    plan memo, so even two DISTINCT ops with one signature hold one plan.
    """
    return KronOp(
        ps, qs, m=m, batch=batch, shared_factors=shared_factors, mesh=mesh,
        data_axis=data_axis, model_axis=model_axis,
        per_iteration=per_iteration, backend=backend, plan=plan, tune=tune,
        cache_path=cache_path, dtype_bytes=dtype_bytes,
        enable_prekron=enable_prekron, n_slabs=n_slabs,
    )


def kron_precond_op(
    p: int, q: int, batch: int, *, dtype_bytes: int = 4, backend: str = "auto"
) -> KronOp:
    """The op behind one Kron-factored-preconditioner shape group.

    A Shampoo-style update ``P_l = A_l G_l B_l`` (per-layer root pairs
    ``A_l = L_l^{-1/4}``, ``B_l = R_l^{-1/4}``) over ``batch`` same-shape
    ``(p, q)`` layers is exactly ONE per-sample-factor batched Kron-Matmul:
    ``x = vec_row(G)`` stacked to ``(B, 1, p*q)``, ``factors = (A, B)``
    stacked to ``((B, p, p), (B, q, q))`` — ``row @ (A (x) B) ==
    vec_row(A^T G B)``, and the roots are symmetric.  Resolved through the
    shared bounded factory so constructing it at step-builder time IS the
    prewarming: the traced update hits this op object, never a re-plan.

    Pre-kronization is forced OFF: densifying ``kron(A_l, B_l)`` is a
    ``(p*q)^2`` buffer per layer per step — the exact materialization the
    Kron-factored preconditioner exists to avoid.
    """
    if batch <= 0:
        raise ValueError(f"batch must be positive, got {batch}")
    return kron_op_for(
        (int(p), int(q)), (int(p), int(q)), m=1, batch=int(batch),
        shared_factors=False, backend=backend, dtype_bytes=dtype_bytes,
        enable_prekron=False,
    )


_DEPRECATION_WARNED: set[str] = set()


def warn_deprecated(name: str, hint: str) -> None:
    """Emit ONE DeprecationWarning per process per legacy entry point."""
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"{name} is deprecated: construct a repro.core.KronOp once "
        f"({hint}) and call it; the shim re-dispatches through a bounded "
        "op cache on every call.",
        DeprecationWarning,
        stacklevel=3,
    )


__all__ = [
    "KronOp",
    "KronCost",
    "kron_op_for",
    "kron_precond_op",
    "signature_of",
    "kron_matmul_p",
    "kron_matmul_batched_p",
]
