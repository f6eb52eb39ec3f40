"""Distributed Kron-Matmul (paper §5, contribution C4) via shard_map.

Device grid ``(G_M, G_K)`` = mesh axes ``(data, model)``; ``X`` is sharded
``P(data, model)``.  Each round performs ``L = N_local`` *local* sliced
multiplies (valid while ``prod(P) | K_loc``), then relocates the distributed
intermediate with ONE ``jax.lax.all_to_all`` + a local transpose.

Why one collective suffices (DESIGN.md §5): after ``L`` local multiplies,
local column ``(q_vec, s)`` on device ``g_k`` is global column
``(q_vec*G_K + g_k)*U + s`` with ``U = K_loc / prod(P)``.  The canonical
redistribution (device d' owns a contiguous stripe) needs exactly the rows
``q_vec`` in d'-th chunk of the q-axis — so: reshape the q-axis into
``(G_K, Q^L/G_K)``, all_to_all the leading chunk axis, swap the received
device axis with the q-chunk axis, flatten.  This is the paper's
STOREGPUTILE index arithmetic expressed as a layout permutation.

Communication per device per round: ``M_loc * C_loc * (G_K-1)/G_K`` elements
with ``ceil(N/L)`` rounds — vs ``N`` rounds for the per-iteration baseline
(CTF / DISTAL), implemented here as ``kron_matmul_distributed_periter`` for
the Figure-11 comparison.

Batched rounds (beyond paper, PR 3): ``kron_matmul_batched_distributed``
carries a whole batch of B independent Kron-Matmul problems through ONE
collective round per stage.  Shared factors collapse B into the data-sharded
M axis and reuse the single-problem round schedule unchanged; per-sample
factors run a batched ``_dist_body`` whose relocation all-to-all moves a
``(B, M_local, C_local)`` slab per stage — one collective for the batch where
a per-problem loop would issue B.  The payload per device per round becomes
``B * M_loc * C_loc * (G_K-1)/G_K`` (``comm_elems_per_device(batch=B)``); the
LATENCY per round is paid once instead of B times, which is the whole win in
the small-problem regime (see EXPERIMENTS.md §Distributed-Batched).  Each
round's local multiplies are ONE chain ``StageInstr`` on the unified emitter
(``kernels/emit.py`` — the same template every other fused path runs; batched
rounds set ``t_b`` from ``autotune.make_batched_plan(g_k=...)``, which trades
it against the per-round relocation slab).

Comm/compute overlap (paper §multi-GPU; the 16-GPU 7.85x): a serial round is
``chain; all_to_all`` — the collective sits on the critical path.  The slab
pipeline splits the row axis into ``n_slabs`` independent slabs and issues
slab ``s-1``'s ``all_to_all`` while slab ``s``'s chain runs (rows are never
communicated, so slabs stay independent across EVERY round: split once before
round 0, concatenate once after the last).  Per round that exposes only one
slab's payload instead of the whole round's — ``comm_hidden_elems`` is the
analytic form of what the pipeline hides, ``KronOp.cost()`` folds it into the
critical-path estimate, and ``autotune.make_batched_plan(g_k=..)`` owns the
``n_slabs``-vs-``t_b`` trade.  Both runners take ``n_slabs`` and share ONE
slab-scheduled body (``_dist_body``; serial = the n=1 degenerate case) with a
custom VJP whose backward rounds pipeline the inverse relocations
symmetrically.  Slab boundaries are row boundaries, so the slabbed schedule
is BITWISE-identical to the serial one, forward and gradients — pinned by
``tests/overlap_distributed_driver.py``.
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..kernels import emit
from ..runtime import chaos, guard, telemetry


# ---------------------------------------------------------------------------
# Static round planning
# ---------------------------------------------------------------------------


def plan_rounds(
    k_loc: int, ps: Sequence[int], qs: Sequence[int], g_k: int,
    *, minimal: bool = False,
) -> list[int]:
    """Split the reversed factor list into rounds of local multiplies.

    Round length L must satisfy (i) ``prod(P) | K_loc`` (all slices stay
    device-local, paper's ``N_local = floor(log_P TG_K)``) and (ii)
    ``G_K | prod(Q)`` (the q-axis can be chunked over devices for the
    relocation).  FastKron (``minimal=False``) takes the LARGEST valid L —
    the paper's communication-minimizing batching; the CTF/DISTAL-style
    baseline (``minimal=True``) relocates as OFTEN as expressible, i.e. the
    smallest valid L (exactly every factor when ``G_K | Q``).  Raises if
    even L=1 is invalid.
    """
    rounds: list[int] = []
    i = 0
    n = len(ps)
    while i < n:
        best = 0
        pprod = qprod = 1
        for j in range(i, n):
            pprod *= ps[j]
            qprod *= qs[j]
            if k_loc % pprod != 0:
                break
            if qprod % g_k == 0:
                best = j - i + 1
                if minimal:
                    break
        if best == 0:
            raise guard.PlanError(
                f"cannot relocate: need G_K={g_k} | prod(Q) for some prefix "
                f"with prod(P) | K_loc={k_loc}; got ps={ps[i:]}, qs={qs[i:]}"
            )
        # advance K_loc through the chosen round
        pprod = math.prod(ps[i : i + best])
        qprod = math.prod(qs[i : i + best])
        k_loc = (k_loc // pprod) * qprod
        rounds.append(best)
        i += best
    return rounds


def comm_elems_per_device(
    m_loc: int, k_loc: int, ps: Sequence[int], qs: Sequence[int], g_k: int,
    rounds: Sequence[int] | None = None, *, batch: int = 1, n_slabs: int = 1,
) -> int:
    """Analytic all_to_all payload (elements sent per device, all rounds).

    ``batch``: number of independent problems riding the SAME collective
    round (``kron_matmul_batched_distributed``) — each round's slab is
    ``batch * M_loc * C * (G_K-1)/G_K`` elements.  The round COUNT does not
    change with ``batch``: that is the latency amortization the batched path
    exists for (a per-problem loop pays ``batch`` times the rounds instead).

    ``n_slabs``: accepted for signature symmetry with the slab-pipelined
    schedule and deliberately inert — slabs REPARTITION each round's payload
    (``m_loc`` rows split into equal row slabs, each relocated by its own
    all_to_all), they never change the total.  The per-slab payloads sum
    exactly to this value because slab counts are clamped to divisors of the
    row axis (``emit.effective_slabs``) and every round's column count is a
    multiple of ``G_K``; the comm-accounting test pins the identity.  What
    overlap changes is the EXPOSED fraction — see ``comm_hidden_elems``.
    """
    del n_slabs  # total is slab-invariant by construction (docstring)
    ps, qs = list(ps), list(qs)
    if rounds is None:
        rounds = plan_rounds(k_loc, ps, qs, g_k)
    total = 0
    i = 0
    c = k_loc
    for r in rounds:
        pprod = math.prod(ps[i : i + r])
        qprod = math.prod(qs[i : i + r])
        c = (c // pprod) * qprod
        total += batch * m_loc * c * (g_k - 1) // g_k
        i += r
    return total


def comm_hidden_elems(
    m_loc: int, k_loc: int, ps: Sequence[int], qs: Sequence[int], g_k: int,
    rounds: Sequence[int] | None = None, *, batch: int = 1, n_slabs: int = 1,
) -> int:
    """Overlap term of the slab pipeline: of the ``comm_elems_per_device``
    total, the elements whose transfer the schedule can hide under a
    neighbouring slab's chain compute (``KronCost.comm_hidden_elems``).

    Per round the pipeline exposes exactly one slab's payload — the last
    slab's all_to_all has nothing left to overlap — so the hidden share is
    ``payload - payload/n`` with ``n`` clamped to the row axis exactly like
    the executor clamps (``emit.effective_slabs``).  The division is exact:
    ``n | m_loc`` and ``G_K | C`` make the per-slab payload an integer, which
    is also why the slab payloads reconcile with the per-slab telemetry
    gauges in ``KronOp.profile()``.  ``n_slabs=1`` (the serial schedule) and
    ``g_k=1`` (no collectives at all) hide nothing.  This is an upper bound
    on real hardware — it assumes each slab's chain is long enough to cover a
    slab transfer; the measured tuner, not this bound, owns the final
    slabbed-vs-serial call (host-mesh collectives run at memcpy speed).
    """
    n = emit.effective_slabs(m_loc, n_slabs)
    if n <= 1 or g_k <= 1:
        return 0
    ps, qs = list(ps), list(qs)
    if rounds is None:
        rounds = plan_rounds(k_loc, ps, qs, g_k)
    hidden = 0
    i = 0
    c = k_loc
    for r in rounds:
        pprod = math.prod(ps[i : i + r])
        qprod = math.prod(qs[i : i + r])
        c = (c // pprod) * qprod
        payload = batch * m_loc * c * (g_k - 1) // g_k
        hidden += payload - payload // n
        i += r
    return hidden


# ---------------------------------------------------------------------------
# shard_map body
# ---------------------------------------------------------------------------


def _record_round_comm(shapes: Sequence[tuple], g_k: int, k: int) -> None:
    """Per-round all_to_all payload metrics — static trace-time ints, so the
    one-truthiness-check contract holds and nothing enters the traced HLO.

    ``shapes`` holds one entry PER SLAB (length 1 for the serial schedule).
    Every slab's payload is observed and gauged individually, and the round
    gauge is their sum — which equals the serial schedule's single payload
    because slabs partition the row axis exactly (no double count, no missing
    slab; the comm-accounting test asserts the identity against
    ``comm_elems_per_device``)."""
    if not telemetry.active():
        return
    n = len(shapes)
    total = 0
    for s, shape in enumerate(shapes):
        elems = math.prod(int(d) for d in shape) * (g_k - 1) // g_k
        total += elems
        telemetry.observe("comm_elems_per_device", elems)
        if n > 1:
            telemetry.gauge_set(f"comm.round{k}.slab{s}.elems_per_device", elems)
    telemetry.gauge_set(f"comm.round{k}.elems_per_device", total)


def _relocate(y: jax.Array, q_prod: int, g_k: int, model_axis: str) -> jax.Array:
    """One all_to_all relocation (see module docstring).  The index
    arithmetic lives in ``_relocate_batched``; the single-problem case is
    the batch-of-one view (the extra reshape is a layout no-op under jit)."""
    return _relocate_batched(y[None], q_prod, g_k, model_axis)[0]


def _local_multiply_round(
    y: jax.Array, fs: Sequence[jax.Array], backend: str, t_b: int | None
) -> jax.Array:
    """One round's local multiplies as ONE chain instruction on the unified
    emitter — the same template every other fused path runs.  ``t_b=None``
    is the single-problem body (2-D operands); an int selects the batch-grid
    kernels with ``t_b`` samples per block, tiles re-fitted per round because
    the round grouping follows the COMM schedule, not the compute plan."""
    fs = tuple(fs)
    off = 0 if t_b is None else 1
    ps = [int(f.shape[off]) for f in fs]
    qs = [int(f.shape[off + 1]) for f in fs]
    tb, t_m, t_k = _round_tiles(
        int(y.shape[-2]), int(y.shape[-1]), ps, qs, t_b or 1
    )
    instr = emit.StageInstr(
        kind=emit.MULTIPLY, ps=tuple(ps), qs=tuple(qs), t_m=t_m, t_k=t_k,
        t_b=None if t_b is None else tb,
    )
    try:
        chaos.maybe_fail("round_chain")
        return emit.run_stage(y, fs, instr, backend=backend)
    except guard.KronError as e:
        # Round chain cannot fit VMEM even at the degenerate tile (huge
        # Q-growth rounds): fall back to per-factor multiplies — the
        # pre-refactor behavior of the single-problem rounds, batch-
        # polymorphic through the engine's conservative fallback.  Same
        # contraction, same one-collective-per-round schedule (the fallback
        # is strictly local) — the property pinned by the chaos driver.
        from .engine import _sliced_batched

        guard.record_event("round_per_factor", e)
        guard.warn_once(
            ("round_per_factor", tuple(ps), tuple(qs)),
            f"kron guard: round chain {ps}x{qs} degraded to per-factor "
            f"multiplies ({type(e).__name__}: {e})",
        )
        for f in fs:
            y = _sliced_batched(y, f, backend)
        return y


# ---------------------------------------------------------------------------
# Shared (single AND batched) slab-scheduled shard_map body
# ---------------------------------------------------------------------------


def _relocate_batched(y: jax.Array, q_prod: int, g_k: int, model_axis: str) -> jax.Array:
    """One all_to_all relocation for the WHOLE batch (the canonical
    implementation — ``_relocate`` is the batch-of-one view).

    The collective moves one ``(B, M_loc, C)`` slab per round instead of B
    separate ``(M_loc, C)`` payloads — same bytes, 1/B the latency."""
    chaos.maybe_fail("collective")
    b, m_loc, c = y.shape
    u = c // q_prod
    chunk = q_prod // g_k
    y5 = y.reshape(b, m_loc, g_k, chunk, u)
    y5 = jax.lax.all_to_all(y5, model_axis, split_axis=2, concat_axis=2)
    # axis 2 is now the sender index g_k; target local col = (q_lo*G_K+g_k)*U+s
    y5 = jnp.swapaxes(y5, 2, 3)
    return y5.reshape(b, m_loc, c)


def _round_tiles(
    m: int, k: int, ps: Sequence[int], qs: Sequence[int], t_b: int
) -> tuple[int, int, int]:
    """(t_b, t_m, t_k) for one round chain that provably fits the unified
    kernel's VMEM legality (``t_b * t_m * t_k * growth <= budget``).
    The round grouping follows the COMM schedule, not the compute plan's
    stages, so tiles are re-fitted here; prefers the planner's ``t_b`` and
    trades it down only if even (t_m=1, t_s=1) cannot hold it."""
    from ..kernels.emit import VMEM_BUDGET_ELEMS, fused_growth

    pprod = math.prod(ps)
    s = k // pprod
    growth = fused_growth(list(ps), list(qs), None)
    for tb in sorted({d for d in range(1, t_b + 1) if t_b % d == 0}, reverse=True):
        t_m = min(8, m)
        while m % t_m:
            t_m -= 1
        while t_m >= 1:
            fits = [
                d for d in range(1, s + 1)
                if s % d == 0 and tb * t_m * d * pprod * growth <= VMEM_BUDGET_ELEMS
            ]
            if fits:
                return tb, t_m, max(fits) * pprod
            t_m = max((d for d in range(1, t_m) if m % d == 0), default=0)
    return 1, 1, pprod  # degenerate problems; XLA path ignores tiles anyway


def _relocate_batched_t(
    y: jax.Array, q_prod: int, g_k: int, model_axis: str
) -> jax.Array:
    """Linear transpose of ``_relocate_batched`` — also its inverse, since a
    relocation is a pure layout permutation: undo the chunk flatten, undo the
    swap, and apply the all_to_all again (``split_axis == concat_axis`` makes
    it an involution).  The backward rounds run this in place of the forward
    relocation, so the slab pipeline overlaps symmetrically under grad."""
    b, m_loc, c = y.shape
    u = c // q_prod
    chunk = q_prod // g_k
    y5 = y.reshape(b, m_loc, chunk, g_k, u)
    y5 = jnp.swapaxes(y5, 2, 3)
    y5 = jax.lax.all_to_all(y5, model_axis, split_axis=2, concat_axis=2)
    return y5.reshape(b, m_loc, c)


def _relocate_slab(
    y: jax.Array, q_prod: int, g_k: int, model_axis: str, n_slabs: int
) -> jax.Array:
    """Relocate ONE slab (2-D single-problem or 3-D batched).  Pipelined
    schedules (``n_slabs > 1``) get their own chaos site so tests can fail a
    single slab's collective mid-round and pin the slabbed → serial-rounds →
    local degradation ladder."""
    if n_slabs > 1:
        chaos.maybe_fail("slab_collective")
    if y.ndim == 2:
        return _relocate(y, q_prod, g_k, model_axis)
    return _relocate_batched(y, q_prod, g_k, model_axis)


def _relocate_slab_t(
    g: jax.Array, q_prod: int, g_k: int, model_axis: str, n_slabs: int
) -> jax.Array:
    """Transposed twin of ``_relocate_slab`` for the backward rounds."""
    if n_slabs > 1:
        chaos.maybe_fail("slab_collective")
    if g.ndim == 2:
        return _relocate_batched_t(g[None], q_prod, g_k, model_axis)[0]
    return _relocate_batched_t(g, q_prod, g_k, model_axis)


def _slab_round(
    slabs: list[jax.Array],
    fs: tuple[jax.Array, ...],
    qprod: int,
    g_k: int,
    model_axis: str,
    backend: str,
    t_b: int | None,
    k: int,
    *,
    record: bool = True,
) -> list[jax.Array]:
    """One slab-scheduled round: run slab ``s``'s chain, and only THEN issue
    slab ``s-1``'s all_to_all — the two are data-independent, so the compiled
    schedule is free to run the collective under the neighbouring slab's
    ``StageInstr`` chain (the double-buffer pipeline; the serial schedule is
    the ``n=1`` degenerate case, which traces to exactly the pre-slab HLO).
    Rows are never communicated, so the returned slabs remain valid
    independent chains for the NEXT round — no per-round re-split."""
    n = len(slabs)
    outs: list[jax.Array] = []
    shapes: list[tuple] = []
    pending = None
    for s in range(n):
        y_s = _local_multiply_round(slabs[s], fs, backend, t_b)
        shapes.append(tuple(int(d) for d in y_s.shape))
        if pending is not None:
            outs.append(_relocate_slab(pending, qprod, g_k, model_axis, n))
        if g_k > 1:
            pending = y_s
        else:
            outs.append(y_s)
    if pending is not None:
        outs.append(_relocate_slab(pending, qprod, g_k, model_axis, n))
    if g_k > 1 and record:
        _record_round_comm(shapes, g_k, k)
    return outs


def _dist_body(
    x_loc: jax.Array,
    factors_rev: tuple[jax.Array, ...],
    *,
    g_k: int,
    model_axis: str,
    backend: str,
    per_iteration: bool,
    t_b: int | None,
    n_slabs: int,
    record: bool = True,
) -> jax.Array:
    """The ONE shard_map body behind both mesh runners: ``t_b=None`` is the
    single-problem schedule (2-D operands, shared factors), an int selects
    the batched per-sample schedule (3-D operands, batch-grid kernels).  The
    row axis is split into ``n_slabs`` slabs ONCE, every round runs the slab
    pipeline (``_slab_round``), and the slabs are concatenated once at the
    end — row-slab boundaries make the result bitwise-identical to the
    serial schedule for any ``n_slabs``."""
    off = 0 if t_b is None else 1
    ps = [int(f.shape[off]) for f in factors_rev]
    qs = [int(f.shape[off + 1]) for f in factors_rev]
    rounds = plan_rounds(int(x_loc.shape[-1]), ps, qs, g_k, minimal=per_iteration)
    n = emit.effective_slabs(int(x_loc.shape[-2]), n_slabs)
    slabs = emit.split_slabs(x_loc, n, axis=-2)
    i = 0
    for k, r in enumerate(rounds):
        fs = tuple(factors_rev[i : i + r])
        qprod = math.prod(qs[i : i + r])
        with telemetry.scope("round"):
            slabs = _slab_round(
                slabs, fs, qprod, g_k, model_axis, backend, t_b, k,
                record=record,
            )
        i += r
    return slabs[0] if n == 1 else jnp.concatenate(slabs, axis=-2)


def _dist_body_bwd(
    x_loc: jax.Array,
    factors_rev: tuple[jax.Array, ...],
    g: jax.Array,
    *,
    g_k: int,
    model_axis: str,
    backend: str,
    per_iteration: bool,
    t_b: int | None,
    n_slabs: int,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """Backward of ``_dist_body`` with the SAME slab pipeline run in reverse:
    per round, slab ``s+1``'s inverse all_to_all is issued while slab ``s``'s
    transposed chain runs, mirroring the forward overlap.

    Bitwise parity with the serial schedule's gradients is structural, not
    numerical luck: per slab the walk only ever computes row-parallel
    transposed multiplies (exact under row splits), and each FACTOR gradient
    is ONE full-row ``_sliced_vjp_factor`` contraction over the concatenated
    slab inputs/cotangents — never a per-slab partial sum, whose float
    association would differ from serial.  Per-round inputs are
    re-materialized from ``x_loc`` (CSE'd against the primal under jit — the
    ``engine._program_bwd`` remat idiom) with telemetry recording off so a
    grad trace does not double-count comm observations."""
    from .engine import _sliced_batched, _sliced_t_batched, _sliced_vjp_factor

    off = 0 if t_b is None else 1
    qs = [int(f.shape[off + 1]) for f in factors_rev]
    rounds = plan_rounds(
        int(x_loc.shape[-1]),
        [int(f.shape[off]) for f in factors_rev],
        qs,
        g_k,
        minimal=per_iteration,
    )
    n = emit.effective_slabs(int(x_loc.shape[-2]), n_slabs)
    slabs = emit.split_slabs(x_loc, n, axis=-2)
    meta: list[tuple[int, tuple, int]] = []
    per_round_in: list[list[jax.Array]] = []
    i = 0
    for k, r in enumerate(rounds):
        fs = tuple(factors_rev[i : i + r])
        qprod = math.prod(qs[i : i + r])
        meta.append((i, fs, qprod))
        per_round_in.append(slabs)
        if k + 1 < len(rounds):
            slabs = _slab_round(
                slabs, fs, qprod, g_k, model_axis, backend, t_b, k,
                record=False,
            )
        i += r

    dfs: list[jax.Array | None] = [None] * len(factors_rev)
    g_slabs = emit.split_slabs(g, n, axis=-2)
    for k in reversed(range(len(rounds))):
        i0, fs, qprod = meta[k]
        with telemetry.scope("round_bwd"):
            def _undo(gs):
                if g_k > 1:
                    return _relocate_slab_t(gs, qprod, g_k, model_axis, n)
                return gs

            inp = [[None] * n for _ in fs]
            cot = [[None] * n for _ in fs]
            new_g: list[jax.Array | None] = [None] * n
            pending = _undo(g_slabs[0])
            for s in range(n):
                # issue slab s+1's inverse relocation first, THEN retire
                # slab s's transposed chain — the mirror of _slab_round
                nxt = _undo(g_slabs[s + 1]) if s + 1 < n else None
                ins = [per_round_in[k][s]]
                for f in fs[:-1]:
                    ins.append(_sliced_batched(ins[-1], f, backend))
                gg = pending
                for idx in reversed(range(len(fs))):
                    inp[idx][s] = ins[idx]
                    cot[idx][s] = gg
                    gg = _sliced_t_batched(gg, fs[idx], backend)
                new_g[s] = gg
                pending = nxt
            for idx, f in enumerate(fs):
                u = inp[idx][0] if n == 1 else jnp.concatenate(inp[idx], axis=-2)
                gg = cot[idx][0] if n == 1 else jnp.concatenate(cot[idx], axis=-2)
                p, q = int(f.shape[-2]), int(f.shape[-1])
                dfs[i0 + idx] = _sliced_vjp_factor(u, gg, p, q).astype(f.dtype)
            g_slabs = new_g
    dx = g_slabs[0] if n == 1 else jnp.concatenate(g_slabs, axis=-2)
    return dx.astype(x_loc.dtype), tuple(dfs)


@lru_cache(maxsize=64)
def _rounds_fn(
    g_k: int,
    model_axis: str,
    backend: str,
    per_iteration: bool,
    t_b: int | None,
    n_slabs: int,
):
    """Custom-VJP round loop for one static config — cached so repeated mesh
    calls reuse one traced callable (the ``engine._kron_fn`` idiom).  The VJP
    exists to keep the BACKWARD rounds slab-pipelined too: plain autodiff
    would transpose the forward graph op-by-op, serializing each inverse
    collective against the transposed chain that produced its operand."""
    cfg = dict(
        g_k=g_k, model_axis=model_axis, backend=backend,
        per_iteration=per_iteration, t_b=t_b, n_slabs=n_slabs,
    )

    @jax.custom_vjp
    def rounds(x_loc, factors_rev):
        return _dist_body(x_loc, factors_rev, **cfg)

    def fwd(x_loc, factors_rev):
        return _dist_body(x_loc, factors_rev, **cfg), (x_loc, factors_rev)

    def bwd(res, g):
        x_loc, factors_rev = res
        with telemetry.scope("op_bwd"):
            return _dist_body_bwd(x_loc, factors_rev, g, **cfg)

    rounds.defvjp(fwd, bwd)
    return rounds


# ---------------------------------------------------------------------------
# Mesh runners (the engine's distributed execution layer) + legacy shims
# ---------------------------------------------------------------------------


def run_distributed_rounds(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mesh: Mesh,
    *,
    data_axis: str | tuple[str, ...] = "data",
    model_axis: str = "model",
    backend: str = "auto",
    per_iteration: bool = False,
    n_slabs: int = 1,
) -> jax.Array:
    """Distributed ``x @ (F^1 (x) ... (x) F^N)`` on a (data, model) mesh —
    the single-problem round schedule the ``KronOp`` mesh path executes.

    ``x``: (M, K) sharded P(data_axis, model_axis); factors replicated
    (paper §5: factors are small and live on every GPU).  Returns (M, K')
    with the same sharding.  ``per_iteration=True`` selects the CTF/DISTAL-
    style baseline that relocates after every factor.  ``n_slabs > 1``
    pipelines each round's all_to_all under the neighbouring row slab's
    chain (bitwise-identical output, clamped to divisors of the local row
    count); the default is the serial schedule — ``KronOp`` owns the choice
    through the planner.
    """
    factors = tuple(factors)
    g_k = mesh.shape[model_axis]
    body = _rounds_fn(
        g_k, model_axis, backend, per_iteration, None, int(n_slabs)
    )
    spec_x = P(data_axis, model_axis)
    fn = jax.shard_map(
        lambda x_loc, fs: body(x_loc, tuple(reversed(fs))),
        mesh=mesh,
        in_specs=(spec_x, P()),
        out_specs=spec_x,
        check_vma=False,
    )
    return fn(x, factors)


def _mesh_size(mesh: Mesh, axis: str | tuple[str, ...]) -> int:
    if isinstance(axis, tuple):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def run_batched_distributed_rounds(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mesh: Mesh,
    *,
    t_b: int = 1,
    data_axis: str | tuple[str, ...] = "data",
    model_axis: str = "model",
    backend: str = "auto",
    per_iteration: bool = False,
    n_slabs: int = 1,
) -> jax.Array:
    """Per-sample-factors batched distributed rounds — the ``KronOp`` mesh
    path for ``shared_factors=False`` (the shared mode collapses B into the
    sharded row axis and runs ``run_distributed_rounds``).

    ``x``: (B, M, K) sharded ``P(None, data_axis, model_axis)``; per-sample
    factors ``F^i: (B, P_i, Q_i)`` replicated.  Each round's local multiplies
    are one batch-grid chain instruction on the emitter (``t_b``
    samples per block) and each round's relocation is ONE all_to_all moving
    the ``(B·M_local, C_local)`` slab — where a per-problem loop would issue
    B collectives per round.  ``n_slabs > 1`` splits the per-sample row axis
    into slabs and pipelines each slab's all_to_all under the next slab's
    chain (``rounds * n_slabs`` collectives carrying the same total payload).
    The plan (``t_b`` and ``n_slabs``) is resolved by the op via
    ``autotune.make_batched_plan(g_k=...)``.
    """
    factors = tuple(factors)
    if x.ndim != 3:
        raise ValueError(f"x must be (B, M, K), got shape {x.shape}")
    if any(f.ndim != 3 for f in factors):
        raise ValueError("expects 3-D (B, P_i, Q_i) per-sample factors")
    b = int(x.shape[0])
    for f in factors:
        if int(f.shape[0]) != b:
            raise ValueError(f"factor batch {f.shape[0]} != x batch {b}")
    body = _rounds_fn(
        mesh.shape[model_axis], model_axis, backend, per_iteration,
        int(t_b), int(n_slabs),
    )
    spec_x = P(None, data_axis, model_axis)
    fn = jax.shard_map(
        lambda x_loc, fs: body(x_loc, tuple(reversed(fs))),
        mesh=mesh,
        in_specs=(spec_x, P()),
        out_specs=spec_x,
        check_vma=False,
    )
    return fn(x, factors)


def kron_matmul_distributed(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mesh: Mesh,
    *,
    data_axis: str | tuple[str, ...] = "data",
    model_axis: str = "model",
    backend: str = "auto",
    per_iteration: bool = False,
) -> jax.Array:
    """DEPRECATED shim over ``KronOp(ps, qs, mesh=mesh)``: distributed
    Kron-Matmul on a (data, model) mesh (see ``run_distributed_rounds``)."""
    from . import engine

    engine.warn_deprecated("kron_matmul_distributed", "KronOp(ps, qs, mesh=mesh)")
    factors = tuple(factors)
    ps, qs = engine.signature_of(factors, shared_factors=True)
    op = engine.kron_op_for(
        ps, qs, mesh=mesh, data_axis=data_axis, model_axis=model_axis,
        backend=backend, per_iteration=per_iteration,
    )
    return op(x, factors)


def kron_matmul_batched_distributed(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mesh: Mesh,
    *,
    shared_factors: bool,
    data_axis: str | tuple[str, ...] = "data",
    model_axis: str = "model",
    backend: str = "auto",
    per_iteration: bool = False,
    plan="auto",
) -> jax.Array:
    """DEPRECATED shim over ``KronOp(ps, qs, batch=B, shared_factors=...,
    mesh=mesh)``: ``B`` independent distributed Kron-Matmuls with ONE
    collective round per stage for the whole batch.

    ``x``: (B, M, K) sharded ``P(None, data_axis, model_axis)``
    (``sharded_input_batched``).  shared_factors=True collapses B into the
    data-sharded M axis (requires ``G_M | B*M``); shared_factors=False runs
    the batch-grid kernels inside ``run_batched_distributed_rounds`` under a
    plan from ``autotune.make_batched_plan(g_k=G_K)`` (``plan=None``: untiled
    ``t_b=1``; or pass an explicit ``KronPlan``).
    """
    from . import engine

    engine.warn_deprecated(
        "kron_matmul_batched_distributed",
        "KronOp(ps, qs, batch=B, shared_factors=..., mesh=mesh)",
    )
    factors = tuple(factors)
    if x.ndim != 3:
        raise ValueError(f"x must be (B, M, K), got shape {x.shape}")
    ps, qs = engine.signature_of(factors, shared_factors=shared_factors)
    op = engine.kron_op_for(
        ps, qs, batch=int(x.shape[0]), shared_factors=shared_factors,
        mesh=mesh, data_axis=data_axis, model_axis=model_axis,
        backend=backend, per_iteration=per_iteration, plan=plan,
    )
    return op(x, factors)


def sharded_input(x, mesh, data_axis="data", model_axis="model"):
    """Place (M, K) onto the grid the distributed algorithm expects."""
    return jax.device_put(x, NamedSharding(mesh, P(data_axis, model_axis)))


def sharded_input_batched(x, mesh, data_axis="data", model_axis="model"):
    """Place (B, M, K) onto the grid ``kron_matmul_batched_distributed``
    expects: batch replicated, rows over ``data_axis``, cols over
    ``model_axis``."""
    return jax.device_put(x, NamedSharding(mesh, P(None, data_axis, model_axis)))


__all__ = [
    "kron_matmul_distributed",
    "kron_matmul_batched_distributed",
    "run_distributed_rounds",
    "run_batched_distributed_rounds",
    "plan_rounds",
    "comm_elems_per_device",
    "comm_hidden_elems",
    "sharded_input",
    "sharded_input_batched",
]
