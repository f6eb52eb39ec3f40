"""Autotuner for FastKron tile sizes + execution plans (contribution C5).

The paper's autotuner compiles ~10k CUDA kernels and times them.  On TPU the
equivalent search space is the Pallas block shapes; since this container has
no TPU, candidates are scored *analytically* with a two-term (compute, HBM)
model that knows the MXU's 128x128 systolic shape and the (8,128) VMEM tile —
the same "narrow by resource limits, then rank" structure as the paper's §4.3.
``tune="measure"`` ranks the narrowed candidates by wall clock instead
(``measure_best``), for use on real hardware — and persists the winner in an
on-disk JSON plan cache keyed by (M, Ps, Qs, dtype, backend) so repeated
calls and the benchmark harness skip both Python planning overhead and
re-measurement (format documented in EXPERIMENTS.md §Plan-cache).

Plan construction additionally decides, per the paper + our beyond-paper
extensions:

  * fusion grouping (C3): how many consecutive factors one kernel chains,
    bounded by ``N_fused = floor(log_P T_K)`` and the VMEM budget — with
    per-factor Q-tiling (``Stage.t_qs``) to keep fusion legal when
    ``prod(Q)/prod(P)`` alone would blow the budget;
  * factor pre-kronization (beyond paper): explicitly form F^i (x) F^{i+1}
    when P is too small to feed the MXU's 128-deep contraction;
  * a BACKWARD plan (``KronPlan.bwd_stages``): the mirrored stages executed
    by the VJP — per-stage transposed chains + factor-gradient contractions —
    with tiles tuned for the transposed shapes;
  * a BATCH tile (``KronPlan.t_b``, ``make_batched_plan``): samples per
    block for the per-sample-factor batch-grid kernels, traded against the
    M-tile — and, in distributed mode (``g_k > 1``), against the per-round
    relocation payload — under the same VMEM budget.

Plan fields and how the planner picks them: docs/architecture.md#kronplan;
cache location/format: docs/api.md#plan-cache.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
import os
import tempfile
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..kernels import emit as emit_mod
from ..kernels import hardware
from ..kernels.emit import StageInstr, StageProgram, fused_growth
from ..runtime import chaos, guard, telemetry
from .kron import KronProblem

# Hardware peaks come from ``kernels.hardware``, keyed by device_kind: the
# attached TPU, or the default target chip when planning off-device.
MXU_DIM = 128
SUBLANE = 8

# Interconnect model for the distributed slab pipeline: the per-device
# all_to_all streams at the chip's ``ici_bw`` and each collective launch pays
# A2A_LATENCY_S regardless of payload.  Slabbing a round multiplies the
# latency term by n_slabs while letting up to (n-1)/n of the payload hide
# under chain compute — so the analytic model only picks n_slabs > 1 once
# per-round payloads clear the ~latency*BW product (~45 KB on v5e), which keeps
# every small test problem on the serial schedule.  Host-mesh collectives
# run at memcpy speed, so ``tune="measure"`` (not this model) owns the final
# call on real fabrics — see ``make_batched_plan``.
A2A_LATENCY_S = 1e-6

PLAN_CACHE_VERSION = 1


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


_divisors = emit_mod._divisors


@dataclasses.dataclass(frozen=True)
class TileConfig:
    t_m: int
    t_s: int  # slices per block (T_K = t_s * P)
    t_q: int

    @property
    def as_tuple(self) -> tuple[int, int, int]:
        return (self.t_m, self.t_s, self.t_q)


def vmem_elems(cfg: TileConfig, p: int, growth: float = 1.0) -> int:
    """f32-elements resident per block (x tile, f tile, y tile), x2 buffered."""
    x_t = cfg.t_m * cfg.t_s * p
    f_t = p * cfg.t_q
    y_t = int(cfg.t_m * cfg.t_q * cfg.t_s * growth)
    return 2 * (x_t + f_t + y_t)


def predict_seconds(
    prob_m: int, s: int, p: int, q: int, cfg: TileConfig, dtype_bytes: int = 4
) -> float:
    """Two-term analytic time model for one sliced multiply on one chip."""
    flops = 2.0 * prob_m * s * p * q
    # MXU utilization: contraction dim padded to 128, lanes to 128, rows to 8.
    u_c = p / _ceil_to(p, MXU_DIM)
    u_q = cfg.t_q / _ceil_to(cfg.t_q, MXU_DIM)
    rows = cfg.t_m * cfg.t_s
    u_r = rows / _ceil_to(rows, SUBLANE)
    hw = hardware.tpu_spec()
    peak = hw.peak_flops_bf16 if dtype_bytes <= 2 else hw.peak_flops_f32
    t_compute = flops / (peak * max(u_c * u_q * u_r, 1e-6))
    # HBM traffic: X re-read once per Q-tile sweep; F negligible; Y written once.
    x_bytes = prob_m * s * p * dtype_bytes * (q // cfg.t_q)
    y_bytes = prob_m * s * q * dtype_bytes
    f_bytes = p * q * dtype_bytes * (prob_m // cfg.t_m) * (s // cfg.t_s)
    t_mem = (x_bytes + y_bytes + f_bytes) / hw.hbm_bw
    return max(t_compute, t_mem)


def _legal_rows(m: int, dtype_bytes: int = 4) -> list[int]:
    """Row tiles a TPU block may have: M, or multiples of the sublane tile."""
    sub = emit_mod._sublane(dtype_bytes)
    return [d for d in _divisors(m) if d == m or d % sub == 0]


def _legal_slices(s: int) -> list[int]:
    """Slice tiles a TPU block may have: S, or multiples of 128 lanes."""
    return [d for d in _divisors(s) if d == s or d % MXU_DIM == 0]


def candidate_tiles(
    m: int, s: int, p: int, q: int, dtype_bytes: int = 4
) -> list[TileConfig]:
    """Paper §4.3 search-space narrowing, restated for Pallas blocks.

    Only legal TPU blocks are proposed: a block's last two dims are tile
    multiples or the full extent, so rows are M or multiples of the 8-row
    sublane tile, slice counts S or multiples of 128 lanes, and Q-tiles Q
    or multiples of 8."""
    t_ms = [t for t in _legal_rows(m, dtype_bytes) if t <= 32] or [m]
    t_ss = [t for t in _legal_slices(s) if t == s or t <= 2048]
    t_qs = [t for t in _divisors(q) if t == q or t % SUBLANE == 0]
    vmem_cap = hardware.tpu_spec().scoped_vmem_bytes * 3 // 4
    out = []
    for t_m, t_s, t_q in itertools.product(t_ms, t_ss, t_qs):
        cfg = TileConfig(t_m, t_s, t_q)
        if vmem_elems(cfg, p) * 4 > vmem_cap:
            continue  # resource-limit pruning (paper: smem + regs cap)
        out.append(cfg)
    return out


def tune_sliced(
    m: int, s: int, p: int, q: int, *, dtype_bytes: int = 4
) -> TileConfig:
    """Best analytic tile config for a single sliced multiply."""
    cands = candidate_tiles(m, s, p, q, dtype_bytes)
    if not cands:
        return TileConfig(min(_legal_rows(m, dtype_bytes)), min(_legal_slices(s)), q)
    return min(cands, key=lambda c: predict_seconds(m, s, p, q, c, dtype_bytes))


def measure_best(
    fn_of_cfg: Callable[[object], Callable[[], jax.Array]],
    cands: Sequence[object],
    *,
    warmup: int = 2,
    iters: int = 5,
) -> tuple[object, float]:
    """Wall-clock ranking of candidates (for real hardware).

    Generic over the candidate type: tile configs for one kernel, or whole
    ``KronPlan``s in ``make_plan(tune="measure")``.  A candidate that fails
    to run with one of the library's typed errors or a runtime (compile /
    execution) error is dropped and recorded as a ``measure_dropped`` event
    in ``guard.health_report()``; any other exception is a bug and
    propagates.
    """
    best, best_t = None, float("inf")
    for cfg in cands:
        try:
            fn = fn_of_cfg(cfg)
            for _ in range(warmup):
                jax.block_until_ready(fn())
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(fn())
            dt = (time.perf_counter() - t0) / iters
        except (guard.KronError, RuntimeError, ValueError) as e:
            guard.record_event("measure_dropped", e)
            continue
        if dt < best_t:
            best, best_t = cfg, dt
    if best is None:
        raise guard.PlanError("no candidate executed successfully")
    return best, best_t


# ---------------------------------------------------------------------------
# Plan: pairing + fusion grouping + tiles per stage (+ mirrored backward)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Stage:
    """One kernel launch: chain ``factor_ids`` (in application order, i.e.
    reversed problem order) inside a single fused kernel.

    ``prekron=True`` means the stage's factors are first combined into their
    explicit Kronecker product (beyond-paper MXU-utilization optimization)
    and applied as ONE sliced multiply.

    ``t_qs`` (fused stages only; application order, one entry per factor)
    tiles the composite Q axis of the fused kernel so its in-VMEM growth is
    bounded by ``prod(t_qs)/prod(P)`` — None means no Q-tiling.

    ``acc_dtype`` (a dtype name, e.g. ``"float32"``) is THIS stage's
    accumulation dtype — per-stage dtype policies flow from here through
    ``lower`` into the emitted kernels and the VJP.  None promotes the input
    dtype against f32 (the historical behavior).
    """

    factor_ids: tuple[int, ...]
    prekron: bool
    tiles: TileConfig
    t_qs: tuple[int, ...] | None = None
    acc_dtype: str | None = None


@dataclasses.dataclass(frozen=True)
class KronPlan:
    stages: tuple[Stage, ...]
    # Backward stages in EXECUTION order (last forward stage first); None
    # falls back to a derived mirror of ``stages`` at run time.
    bwd_stages: tuple[Stage, ...] | None = None
    # Batch tile for the batched (per-sample-factors) execution path: how many
    # samples one kernel block carries.  The batched kernels' VMEM legality is
    # ``t_b * t_m * t_k * growth <= budget`` — make_batched_plan trades the
    # M-tile against this axis.  1 == unbatched semantics (ignored by the
    # single-problem path).
    t_b: int = 1
    # Slab-pipeline depth for the DISTRIBUTED rounds: how many row slabs each
    # mesh round is split into so one slab's all_to_all overlaps the next
    # slab's chain.  1 == the serial round schedule; only the mesh path reads
    # it (local execution ignores it, like the single-problem path ignores
    # t_b).  make_batched_plan(g_k>1) trades this axis against t_b under the
    # VMEM budget: more slabs shrink the resident relocation payload.
    n_slabs: int = 1

    def describe(self) -> str:
        parts = []
        for st in self.stages:
            kind = "prekron" if st.prekron else ("fused" if len(st.factor_ids) > 1 else "sliced")
            tag = f"{kind}{list(st.factor_ids)}@{st.tiles.as_tuple}"
            if st.t_qs is not None:
                tag += f"/tq{list(st.t_qs)}"
            parts.append(tag)
        head = f"[t_b={self.t_b}] " if self.t_b != 1 else ""
        if self.n_slabs != 1:
            head += f"[slabs={self.n_slabs}] "
        return head + " -> ".join(parts)


def mirror_bwd_stages(
    prob: KronProblem, stages: Sequence[Stage], *, dtype_bytes: int = 4
) -> tuple[Stage, ...]:
    """Backward stages for a forward plan: same grouping, reversed execution
    order, tiles tuned for the transposed contraction (P and Q swap roles)."""
    ps = list(reversed(prob.ps))
    qs = list(reversed(prob.qs))
    # Column count at each stage OUTPUT (the backward stage's input).
    k = prob.k
    outs = []
    for st in stages:
        pprod = math.prod(ps[i] for i in st.factor_ids)
        qprod = math.prod(qs[i] for i in st.factor_ids)
        k = k // pprod * qprod
        outs.append((st, pprod, qprod, k))
    bwd = []
    for st, pprod, qprod, k_out in reversed(outs):
        s = k_out // qprod
        tiles = tune_sliced(prob.m, s, qprod, pprod, dtype_bytes=dtype_bytes)
        bwd.append(Stage(st.factor_ids, st.prekron, tiles, st.t_qs, st.acc_dtype))
    return tuple(bwd)


def lower(
    plan: KronPlan,
    ps: Sequence[int],
    qs: Sequence[int],
    *,
    batched: bool = False,
    acc_dtype: str | None = None,
) -> StageProgram:
    """Lower a ``KronPlan`` into the emitter's ``StageProgram`` IR.

    This is the single contract between planning and execution: one typed
    instruction per stage (``multiply`` or ``prekron``), each carrying its
    per-factor ``(p_i, q_i)`` list, its tiles (``t_k = t_s * prod(P)``), its
    batch tile (``t_b=None`` when ``batched=False`` — batch is then just a
    leading grid axis, not a separate code path), its accumulation dtype
    (``Stage.acc_dtype``, falling back to ``acc_dtype``), and the tuned
    transposed M-tile from ``plan.bwd_stages`` so ``emit.transpose`` can swap
    it in mechanically.  ``ps``/``qs`` are the problem-order factor dims.
    """
    rps = tuple(reversed(tuple(int(p) for p in ps)))
    rqs = tuple(reversed(tuple(int(q) for q in qs)))
    bwd_sts = plan.bwd_stages or tuple(reversed(plan.stages))
    n_st = len(plan.stages)
    instrs = []
    for i, st in enumerate(plan.stages):
        sps = tuple(rps[j] for j in st.factor_ids)
        sqs = tuple(rqs[j] for j in st.factor_ids)
        bst = bwd_sts[n_st - 1 - i]
        t_qs = st.t_qs
        if t_qs is None and (st.prekron or len(st.factor_ids) == 1):
            # Single-multiply stages (one factor, or a prekron product): the
            # stage's TUNED Q-tile is tiles.t_q — without it the chain
            # template would see full Q and huge-Q factors would fail the
            # VMEM growth check that the old kron_sliced kernel's t_q tiling
            # made irrelevant.  Injected ONLY when full-Q growth actually
            # overflows the budget: everything else keeps t_qs=None so the
            # emitted grid matches the pre-refactor kernels exactly, and
            # placeholder tiles (t_q=1 in engine-built fallback plans) are
            # never mistaken for a tuned Q-tile.  Prekron stages' tiles are
            # tuned for the combined product, so the 1-tuple applies to it
            # (run_stage keeps a length-1 t_qs across the substitution).
            eff_p = math.prod(sps)
            eff_q = math.prod(sqs)
            t_k = st.tiles.t_s * eff_p
            full = st.tiles.t_m * t_k * max(1.0, eff_q / eff_p)
            if (
                (plan.t_b if batched else 1) * full > emit_mod.VMEM_BUDGET_ELEMS
                and 1 < st.tiles.t_q < eff_q
                and eff_q % st.tiles.t_q == 0
            ):
                t_qs = (st.tiles.t_q,)
        instrs.append(
            StageInstr(
                kind=emit_mod.PREKRON if st.prekron else emit_mod.MULTIPLY,
                ps=sps,
                qs=sqs,
                factor_ids=st.factor_ids,
                t_m=st.tiles.t_m,
                t_k=st.tiles.t_s * math.prod(sps),
                t_qs=t_qs,
                t_b=plan.t_b if batched else None,
                acc_dtype=st.acc_dtype if st.acc_dtype is not None else acc_dtype,
                t_m_bwd=bst.tiles.t_m,
            )
        )
    return StageProgram(tuple(instrs), len(rps))


def _chip_fits(m: int, k: int, ps, qs, dtype_bytes: int) -> bool:
    """Whether a stage chaining ``ps``/``qs`` over (m, k) has a legal compiled
    tiling for both its forward and its stage-backward kernel."""
    return all(
        emit_mod.legal_tiles(
            "fwd", 1, m, k, ps, qs, t_b=1, t_m=8, itemsize=dtype_bytes,
            grad=grad,
        )
        is not None
        for grad in (False, True)
    )


def make_plan(
    prob: KronProblem,
    *,
    dtype_bytes: int = 4,
    enable_fusion: bool = True,
    enable_prekron: bool = True,
    prekron_max_p: int = 16,
    prekron_max_dim: int = 256,
    vmem_budget_elems: int = 2 * 1024 * 1024,
    tune: str = "analytic",
    backend: str = "auto",
    cache_path: str | None = None,
    acc_dtype: str | None = None,
) -> KronPlan:
    """Greedy plan over the reversed factor list (application order).

    Stage selection per position i (0 = last factor, applied first):
      1. If P_i and P_{i+1} are both small, pre-kronize the pair (MXU win).
      2. Else fuse as many consecutive factors as N_fused/VMEM allow (C3),
         Q-tiling factors whose growth would otherwise end the group.
      3. Else a single tuned sliced multiply.

    ``acc_dtype`` stamps every stage's accumulation dtype (per-stage policies
    are set by replacing individual ``Stage.acc_dtype`` fields); None keeps
    the promote-against-f32 default.

    ``tune="measure"`` wall-clock-ranks a narrowed set of plan variants via
    ``measure_best`` — the candidates are EMITTED as StagePrograms and timed
    through ``kernels.emit`` — and memoizes the winner in the on-disk plan
    cache.
    """
    if tune == "measure":
        return _measured_plan(
            prob,
            dtype_bytes=dtype_bytes,
            enable_fusion=enable_fusion,
            enable_prekron=enable_prekron,
            prekron_max_p=prekron_max_p,
            prekron_max_dim=prekron_max_dim,
            vmem_budget_elems=vmem_budget_elems,
            backend=backend,
            cache_path=cache_path,
            acc_dtype=acc_dtype,
        )
    if tune != "analytic":
        raise guard.PlanError(f"unknown tune mode {tune!r}")
    ps = list(reversed(prob.ps))
    qs = list(reversed(prob.qs))
    n = len(ps)
    stages: list[Stage] = []
    k = prob.k
    i = 0
    on_chip = emit_mod.resolve_backend(backend) == "pallas"
    while i < n:
        p, q = ps[i], qs[i]
        # -- beyond-paper pre-kronization --
        if (
            enable_prekron
            and i + 1 < n
            and p <= prekron_max_p
            and ps[i + 1] <= prekron_max_p
            and p * ps[i + 1] <= prekron_max_dim
            and q * qs[i + 1] <= prekron_max_dim
        ):
            pp, qq = p * ps[i + 1], q * qs[i + 1]
            s = k // pp
            tiles = tune_sliced(prob.m, s, pp, qq, dtype_bytes=dtype_bytes)
            stages.append(Stage((i, i + 1), True, tiles, None, acc_dtype))
            k = s * qq
            i += 2
            continue
        # -- C3 fusion grouping (VMEM-bounded, with Q-tiling relief) --
        group = [i]
        group_tqs = [q]
        if enable_fusion:
            pprod, tqprod = p, q
            j = i + 1
            while j < n:
                np_ = pprod * ps[j]
                if np_ > k:
                    break  # N_fused cap: T_K can hold at most log_P K factors
                # Largest Q-tile of factor j whose growth fits the budget with
                # a T_M of 8 (T_K refined below); full Q when it already fits.
                tq_j = None
                for cand in sorted(_divisors(qs[j]), reverse=True):
                    growth = max(1.0, tqprod * cand / np_)
                    if 8 * np_ * growth * 4 <= vmem_budget_elems:
                        tq_j = cand
                        break
                if tq_j is None:
                    break
                if on_chip and not _chip_fits(
                    prob.m, k, [ps[g] for g in group] + [ps[j]],
                    [qs[g] for g in group] + [qs[j]], dtype_bytes,
                ):
                    break  # no legal kernel tiling holds the longer chain
                pprod, tqprod = np_, tqprod * tq_j
                group.append(j)
                group_tqs.append(tq_j)
                j += 1
        pprod = math.prod(ps[g] for g in group)
        qprod = math.prod(qs[g] for g in group)
        s = k // pprod
        if len(group) > 1:
            # Repair pass: the grouping loop's fit proxy measures growth
            # against the RUNNING prefix product, but the emitted tile's
            # T_K is a multiple of the FULL prod(P) — and the first factor
            # is admitted with full Q unchecked — so early-prefix growth
            # can exceed the budget even at the minimal (t_m=1, t_s=1)
            # tile.  Shrink the worst-contributing Q-tile until it fits
            # (t_qs=1 everywhere bounds growth at 1, so this terminates).
            sps = [ps[g] for g in group]
            sqs = [qs[g] for g in group]
            while (
                pprod * fused_growth(sps, sqs, group_tqs) > vmem_budget_elems
                and any(t > 1 for t in group_tqs)
            ):
                i_big = max(
                    range(len(group_tqs)),
                    key=lambda j: group_tqs[j] / sps[j],
                )
                group_tqs[i_big] = max(
                    (d for d in _divisors(sqs[i_big]) if d < group_tqs[i_big]),
                    default=1,
                )
        tiles = tune_sliced(prob.m, s, pprod, qprod, dtype_bytes=dtype_bytes)
        t_qs = tuple(group_tqs) if group_tqs != [qs[g] for g in group] else None
        if len(group) > 1:
            # Clamp (T_M, T_K = t_s * prod(P)) so the fused tile respects the
            # budget (the grouping loop guaranteed a fit at T_M=8, t_s=1).
            growth = fused_growth([ps[g] for g in group], [qs[g] for g in group], t_qs)
            t_m = tiles.t_m
            rows = _legal_rows(prob.m, dtype_bytes)
            while t_m > rows[0] and t_m * pprod * growth > vmem_budget_elems:
                t_m = max(d for d in rows if d < t_m)
            max_ts = max(1, int(vmem_budget_elems // (t_m * pprod * growth)))
            ts = tiles.t_s
            if ts > max_ts:
                slices = _legal_slices(s)
                ts = max((d for d in slices if d <= max_ts), default=slices[0])
            if (t_m, ts) != (tiles.t_m, tiles.t_s):
                tiles = TileConfig(t_m, ts, tiles.t_q)
        stages.append(Stage(tuple(group), False, tiles, t_qs, acc_dtype))
        k = s * qprod
        i = group[-1] + 1
    fwd = tuple(stages)
    return KronPlan(fwd, mirror_bwd_stages(prob, fwd, dtype_bytes=dtype_bytes))


# ---------------------------------------------------------------------------
# Batched plans: B independent problems (kron_matmul_batched)
# ---------------------------------------------------------------------------


def _dist_round_payload_elems(prob: KronProblem, g_k: int) -> int:
    """Worst-round per-sample relocation slab for the batched DISTRIBUTED
    path: one device's all_to_all staging buffer holds ``M_loc * C`` elements
    per sample at the round's output width ``C`` (the ``(G_K-1)/G_K`` send
    fraction still occupies the buffer — received chunks land in place).
    ``prob`` is the LOCAL problem (``m = M_loc``); columns start at
    ``K / G_K``.  Returns 0 when the mesh has no model axis or the round
    schedule is infeasible (the caller then plans compute-only)."""
    if g_k <= 1:
        return 0
    from .distributed import plan_rounds

    ps = list(reversed(prob.ps))
    qs = list(reversed(prob.qs))
    k_loc = prob.k // g_k
    try:
        rounds = plan_rounds(k_loc, ps, qs, g_k)
    except ValueError:
        return 0
    worst = 0
    c = k_loc
    i = 0
    for r in rounds:
        c = c // math.prod(ps[i : i + r]) * math.prod(qs[i : i + r])
        worst = max(worst, prob.m * c)
        i += r
    return worst


def _dist_round_costs(
    prob: KronProblem, g_k: int, batch: int, dtype_bytes: int
) -> list[tuple[float, float]]:
    """Per-round ``(compute_s, comm_s)`` on one device of the mesh round
    schedule: chain flops against the dtype's peak, all_to_all payload
    against the chip's ``ici_bw``.  ``prob`` is the LOCAL problem (``m = M_loc``).
    Raises ``PlanError`` when no round schedule exists (callers fall back to
    the serial schedule)."""
    from .distributed import plan_rounds

    ps = list(reversed(prob.ps))
    qs = list(reversed(prob.qs))
    k_loc = prob.k // g_k
    rounds = plan_rounds(k_loc, ps, qs, g_k)
    hw = hardware.tpu_spec()
    peak = hw.peak_flops_bf16 if dtype_bytes <= 2 else hw.peak_flops_f32
    costs = []
    c = k_loc
    i = 0
    for r in rounds:
        flops = 0.0
        for j in range(i, i + r):
            flops += 2.0 * batch * prob.m * c * qs[j]
            c = c // ps[j] * qs[j]
        payload = batch * prob.m * c * (g_k - 1) / g_k
        costs.append((flops / peak, payload * dtype_bytes / hw.ici_bw))
        i += r
    return costs


def _slab_schedule_seconds(
    costs: Sequence[tuple[float, float]], n_slabs: int
) -> float:
    """Analytic time of the slab-pipelined round schedule: per round, up to
    ``(n-1)/n`` of the overlappable ``min(compute, comm)`` hides, and every
    slab's all_to_all pays the launch latency.  ``n_slabs=1`` recovers the
    serial ``compute + comm + latency`` sum."""
    total = 0.0
    for comp, comm in costs:
        hidden = min(comp, comm) * (n_slabs - 1) / n_slabs
        total += comp + comm - hidden + n_slabs * A2A_LATENCY_S
    return total


def choose_n_slabs(
    prob: KronProblem,
    g_k: int,
    *,
    batch: int = 1,
    dtype_bytes: int = 4,
    candidates: Sequence[int] = (1, 2, 4),
) -> int:
    """Analytic slab count for the distributed round pipeline.

    ``prob`` is the LOCAL problem (``m = M_loc`` — the slab axis; for the
    shared-factors path that is the collapsed ``B*M/G_M`` row count).  Each
    candidate is clamped to a divisor of the row axis, scored with
    ``_slab_schedule_seconds``, and the serial schedule wins ties — the
    latency term means slabbing only pays once per-round payloads clear
    roughly ``A2A_LATENCY_S * ici_bw`` (~45 KB per collective), so small
    problems always plan serial.  This is the HBM-class analytic model;
    ``make_batched_plan(tune="measure", mesh=...)`` overrules it with a wall
    clock on the emitted program."""
    if g_k <= 1 or prob.m <= 1:
        return 1
    try:
        costs = _dist_round_costs(prob, g_k, batch, dtype_bytes)
    except guard.PlanError:
        return 1
    best_n, best_t = 1, _slab_schedule_seconds(costs, 1)
    for n in candidates:
        n_eff = emit_mod.effective_slabs(prob.m, n)
        if n_eff == best_n:
            continue
        t = _slab_schedule_seconds(costs, n_eff)
        if t < best_t:
            best_n, best_t = n_eff, t
    return best_n


def _batch_tiled(
    base: KronPlan,
    prob: KronProblem,
    batch: int,
    vmem_budget_elems: int,
    dtype_bytes: int,
    extra_per_sample_elems: int = 0,
) -> KronPlan:
    """Batch-aware tiling for the per-sample batch-grid kernels.

    A block of the batched kernel holds ``t_b`` sample chains, so the budget
    constraint becomes ``t_b * t_m * t_k * growth <= budget``.  Small-M
    batched problems amortize grid steps across samples, so the M-tile is
    traded DOWN to buy batch tiles: while ``t_b`` is below the sublane width
    (8 rows is what the TPU needs to fill a register row anyway), the largest
    stage M-tile is reduced and ``t_b`` recomputed under the same budget.

    ``extra_per_sample_elems`` (distributed mode): per-sample elements that
    share the budget with the compute block — the per-round relocation slab —
    so the effective constraint is ``t_b * (block + extra) <= budget``.  This
    is the t_b-vs-payload trade: a bigger batch tile buys launch amortization
    but inflates the round's resident communication slab.
    """
    ps = list(reversed(prob.ps))
    qs = list(reversed(prob.qs))
    stages = list(base.stages)

    def block_elems(st: Stage) -> float:
        sps = [ps[i] for i in st.factor_ids]
        sqs = [qs[i] for i in st.factor_ids]
        t_k = st.tiles.t_s * math.prod(sps)
        return st.tiles.t_m * t_k * fused_growth(sps, sqs, st.t_qs)

    def best_t_b() -> int:
        worst = max(block_elems(st) for st in stages) + extra_per_sample_elems
        cap = max(1, int(vmem_budget_elems // max(worst, 1.0)))
        return max(d for d in _divisors(batch) if d <= cap)

    t_b = best_t_b()
    rows = _legal_rows(prob.m, dtype_bytes)
    while t_b < min(batch, SUBLANE):
        reducible = [i for i, st in enumerate(stages) if st.tiles.t_m > rows[0]]
        if not reducible:
            break
        i = max(reducible, key=lambda i: stages[i].tiles.t_m)
        st = stages[i]
        new_tm = max(d for d in rows if d < st.tiles.t_m)
        stages[i] = dataclasses.replace(
            st, tiles=TileConfig(new_tm, st.tiles.t_s, st.tiles.t_q)
        )
        t_b = max(t_b, best_t_b())
    fwd = tuple(stages)
    return KronPlan(
        fwd, mirror_bwd_stages(prob, fwd, dtype_bytes=dtype_bytes), t_b
    )


def make_batched_plan(
    prob: KronProblem,
    batch: int,
    *,
    shared_factors: bool = True,
    dtype_bytes: int = 4,
    enable_fusion: bool = True,
    enable_prekron: bool = False,
    prekron_max_p: int = 16,
    prekron_max_dim: int = 256,
    vmem_budget_elems: int = 2 * 1024 * 1024,
    tune: str = "analytic",
    backend: str = "auto",
    cache_path: str | None = None,
    g_k: int = 1,
    acc_dtype: str | None = None,
    mesh=None,
    data_axis="data",
    model_axis: str = "model",
) -> KronPlan:
    """Plan for ``batch`` independent copies of ``prob`` in one launch.

    shared_factors=True (one factor set, batched X): the batch collapses into
    M, so this is the single-problem planner on the ``(batch*M, Ps, Qs)``
    problem — the M-tile is tuned for the collapsed row count.

    shared_factors=False (per-sample factors): the single-problem plan is
    re-tiled by ``_batch_tiled`` so every stage block carries ``t_b`` samples
    under the same VMEM budget.  ``enable_prekron=True`` lets the planner
    emit pre-kronization stages here too — the batched executor runs them as
    a vmapped ``jnp.kron`` + one batched sliced multiply
    (``emit.prekron_product`` inside ``run_stage``); callers enable it where
    the analytic model
    favors it (TPU MXU, same gate as the single-problem path).
    ``tune="measure"`` wall-clock ranks ``t_b`` variants BY MEASURING THE
    EMITTED PROGRAM (the same ``_measured_plan``/``measure_best`` path the
    single-problem planner uses — one measured path, not a split) and
    persists the winner keyed on B, with the widened candidate set recorded
    in the plan-cache entry.

    ``g_k > 1`` selects DISTRIBUTED mode (``kron_matmul_batched_distributed``
    on a mesh with a ``G_K``-way model axis): ``prob`` is the per-device
    LOCAL problem (``m = M_loc``).  The plan gains TWO distributed axes,
    traded jointly under the VMEM budget: the batch tile ``t_b`` and the
    slab-pipeline depth ``n_slabs``.  For each candidate slab count the
    worst-round relocation slab (``_dist_round_payload_elems``) SHRINKS by
    the slab factor — only one slab's payload is resident at a time — so the
    constraint is ``t_b * (block + payload/n) <= budget``: more slabs buy
    back batch tiles.  Candidates are scored with the analytic overlap model
    (``_slab_schedule_seconds``: hidden comm vs the per-slab collective
    latency), which keeps small problems on the serial schedule.  With
    ``tune="measure"`` AND a ``mesh``, candidates are instead wall-clock
    ranked on the emitted program through the real mesh runner and persisted
    in the plan cache under a key with a ``;gk=`` component
    (``_measured_dist_plan``) — host-mesh collectives run at memcpy speed,
    so measuring is the only honest way to rank slabbed vs serial schedules
    off-fabric; without a mesh, measure falls back to the analytic
    distributed plan and nothing is cached.  Distributed SHARED-factor plans
    do not exist: the shared path collapses B into the sharded row axis and
    needs no batched plan, so ``g_k > 1`` with ``shared_factors=True``
    raises rather than silently planning a single-device problem.
    """
    if batch <= 0:
        raise ValueError(f"batch must be positive, got {batch}")
    if g_k > 1 and shared_factors:
        raise ValueError(
            "g_k > 1 (distributed mode) requires shared_factors=False: the "
            "shared-factors distributed path collapses the batch into the "
            "data-sharded row axis and takes no batched plan"
        )
    if g_k > 1 and not shared_factors:
        if tune == "measure" and mesh is not None:
            return _measured_dist_plan(
                prob,
                batch=batch,
                g_k=g_k,
                mesh=mesh,
                data_axis=data_axis,
                model_axis=model_axis,
                dtype_bytes=dtype_bytes,
                enable_fusion=enable_fusion,
                vmem_budget_elems=vmem_budget_elems,
                backend=backend,
                cache_path=cache_path,
                acc_dtype=acc_dtype,
            )
        return _analytic_dist_plan(
            prob, batch, g_k,
            dtype_bytes=dtype_bytes,
            enable_fusion=enable_fusion,
            vmem_budget_elems=vmem_budget_elems,
            backend=backend,
            acc_dtype=acc_dtype,
        )
    if shared_factors:
        return make_plan(
            KronProblem(batch * prob.m, prob.ps, prob.qs),
            dtype_bytes=dtype_bytes,
            enable_fusion=enable_fusion,
            enable_prekron=enable_prekron,
            prekron_max_p=prekron_max_p,
            prekron_max_dim=prekron_max_dim,
            vmem_budget_elems=vmem_budget_elems,
            tune=tune,
            backend=backend,
            cache_path=cache_path,
            acc_dtype=acc_dtype,
        )
    if tune == "measure":
        return _measured_plan(
            prob,
            batch=batch,
            dtype_bytes=dtype_bytes,
            enable_fusion=enable_fusion,
            enable_prekron=enable_prekron,
            prekron_max_p=prekron_max_p,
            prekron_max_dim=prekron_max_dim,
            vmem_budget_elems=vmem_budget_elems,
            backend=backend,
            cache_path=cache_path,
            acc_dtype=acc_dtype,
        )
    if tune != "analytic":
        raise guard.PlanError(f"unknown tune mode {tune!r}")
    base = make_plan(
        prob,
        dtype_bytes=dtype_bytes,
        enable_fusion=enable_fusion,
        enable_prekron=enable_prekron,
        prekron_max_p=prekron_max_p,
        prekron_max_dim=prekron_max_dim,
        vmem_budget_elems=vmem_budget_elems,
        tune="analytic",
        backend=backend,
        acc_dtype=acc_dtype,
    )
    return _batch_tiled(base, prob, batch, vmem_budget_elems, dtype_bytes)


def _dist_plan_candidates(
    prob: KronProblem,
    batch: int,
    g_k: int,
    *,
    dtype_bytes: int,
    enable_fusion: bool,
    vmem_budget_elems: int,
    backend: str,
    acc_dtype: str | None,
    slab_candidates: Sequence[int] = (1, 2, 4),
) -> list[KronPlan]:
    """One distributed plan per feasible slab count, serial first.  Each
    candidate re-runs the t_b fit with the per-slab payload share
    (``payload // n``) so deeper pipelines can legitimately carry bigger
    batch tiles — the n_slabs-vs-t_b trade as an explicit candidate axis."""
    base = make_plan(
        prob,
        dtype_bytes=dtype_bytes,
        enable_fusion=enable_fusion,
        enable_prekron=False,
        vmem_budget_elems=vmem_budget_elems,
        tune="analytic",
        backend=backend,
        acc_dtype=acc_dtype,
    )
    payload = _dist_round_payload_elems(prob, g_k)
    cands = []
    for n in sorted({emit_mod.effective_slabs(prob.m, n) for n in slab_candidates}):
        plan_n = _batch_tiled(
            base, prob, batch, vmem_budget_elems, dtype_bytes,
            extra_per_sample_elems=payload // n,
        )
        cands.append(dataclasses.replace(plan_n, n_slabs=n))
    return cands


def _analytic_dist_plan(
    prob: KronProblem, batch: int, g_k: int, *, dtype_bytes, enable_fusion,
    vmem_budget_elems, backend, acc_dtype,
) -> KronPlan:
    """Analytic distributed batched plan: pick the candidate whose slab
    schedule minimizes the overlap model's time; on a tie the BIGGER batch
    tile wins (the whole point of trading the axes), then the shallower
    pipeline (serial is listed first)."""
    cands = _dist_plan_candidates(
        prob, batch, g_k, dtype_bytes=dtype_bytes, enable_fusion=enable_fusion,
        vmem_budget_elems=vmem_budget_elems, backend=backend,
        acc_dtype=acc_dtype,
    )
    try:
        costs = _dist_round_costs(prob, g_k, batch, dtype_bytes)
    except guard.PlanError:
        return cands[0]
    best, best_t = cands[0], _slab_schedule_seconds(costs, cands[0].n_slabs)
    for plan in cands[1:]:
        t = _slab_schedule_seconds(costs, plan.n_slabs)
        if t < best_t or (t == best_t and plan.t_b > best.t_b):
            best, best_t = plan, t
    return best


# ---------------------------------------------------------------------------
# Measured tuning + on-disk plan cache
# ---------------------------------------------------------------------------


def default_cache_path() -> str:
    return os.environ.get(
        "FASTKRON_PLAN_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "fastkron", "plans.json"),
    )


def plan_cache_key(
    prob: KronProblem,
    dtype_bytes: int,
    backend: str,
    *,
    enable_fusion: bool = True,
    enable_prekron: bool = True,
    prekron_max_p: int = 16,
    prekron_max_dim: int = 256,
    vmem_budget_elems: int = 2 * 1024 * 1024,
    batch: int = 0,
    shared_factors: bool = True,
    acc_dtype: str | None = None,
) -> str:
    """Cache key covers every plan-shaping input (defaults mirror make_plan):
    a hit must satisfy the caller's constraints, not just the problem shape.
    ``batch > 0`` marks a batched-plan entry (keyed on B and the factor-
    sharing mode); 0 keeps the single-problem key format stable, and a
    non-default ``acc_dtype`` is appended only when set for the same reason.
    Distributed MEASURED plans (``make_batched_plan(g_k > 1, tune="measure",
    mesh=...)``) append a ``;gk=<G_K>`` component to this key — append-only
    like ``;B=``/``;acc=``, so pre-slab cache files load unchanged and
    single-host entries never collide with distributed ones; analytic
    distributed plans are still never cached."""
    ps = ",".join(map(str, prob.ps))
    qs = ",".join(map(str, prob.qs))
    key = (
        f"m={prob.m};ps={ps};qs={qs};dtype={dtype_bytes};backend={backend}"
        f";fuse={int(enable_fusion)};prekron={int(enable_prekron)}"
        f";pmax={prekron_max_p};pdim={prekron_max_dim};vmem={vmem_budget_elems}"
    )
    if batch > 0:
        key += f";B={batch};shared={int(shared_factors)}"
    if acc_dtype is not None:
        key += f";acc={acc_dtype}"
    return key


def _stage_to_json(st: Stage) -> dict:
    return {
        "factor_ids": list(st.factor_ids),
        "prekron": st.prekron,
        "tiles": list(st.tiles.as_tuple),
        "t_qs": list(st.t_qs) if st.t_qs is not None else None,
        "acc_dtype": st.acc_dtype,
    }


def _stage_from_json(d: dict) -> Stage:
    return Stage(
        tuple(d["factor_ids"]),
        bool(d["prekron"]),
        TileConfig(*d["tiles"]),
        tuple(d["t_qs"]) if d.get("t_qs") is not None else None,
        d.get("acc_dtype"),
    )


def plan_to_json(plan: KronPlan) -> dict:
    return {
        "stages": [_stage_to_json(s) for s in plan.stages],
        "bwd_stages": (
            [_stage_to_json(s) for s in plan.bwd_stages]
            if plan.bwd_stages is not None
            else None
        ),
        "t_b": plan.t_b,
        "n_slabs": plan.n_slabs,
    }


def plan_from_json(d: dict) -> KronPlan:
    return KronPlan(
        tuple(_stage_from_json(s) for s in d["stages"]),
        (
            tuple(_stage_from_json(s) for s in d["bwd_stages"])
            if d.get("bwd_stages") is not None
            else None
        ),
        int(d.get("t_b", 1)),
        int(d.get("n_slabs", 1)),  # pre-slab cache entries default to serial
    )


def load_plan_cache(path: str) -> dict:
    """Best-effort load: a corrupt / truncated / wrong-schema file (e.g. a
    concurrent writer died mid-rename on a non-atomic filesystem) degrades to
    an empty cache, never an exception — the next save rewrites it whole.
    Corruption is routed through ``PlanCacheError`` bookkeeping: a once-per-
    process ``GuardWarning`` plus a ``plan_cache_rebuild`` health event, so
    lost tuning work is visible instead of silent.  A missing file or a
    version bump is a normal condition and stays quiet."""
    try:
        chaos.maybe_fail("plan_cache_load")
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:  # PlanCacheError is an OSError
        guard.record_event("plan_cache_rebuild", guard.PlanCacheError(str(e)))
        guard.warn_once(
            ("plan_cache_load", path),
            f"kron guard: plan cache at {path!r} unreadable "
            f"({type(e).__name__}: {e}) — rebuilding from scratch",
        )
        return {}
    if not isinstance(data, dict) or data.get("version") != PLAN_CACHE_VERSION:
        return {}
    entries = data.get("entries", {})
    if not isinstance(entries, dict):
        return {}
    return {
        k: v
        for k, v in entries.items()
        if isinstance(v, dict) and isinstance(v.get("plan"), dict)
    }


PLAN_CACHE_SAVE_RETRIES = 3


def save_plan_cache(
    path: str, entries: dict, *, retries: int = PLAN_CACHE_SAVE_RETRIES
) -> None:
    """Atomic write: temp file in the target directory + ``os.replace`` so a
    reader never sees a partial file and concurrent benchmark/CI runs can't
    poison each other.  On-disk entries written since our load are merged in
    (ours win on key conflict) so parallel tuners lose at most a race, not
    their work.  Lock/rename contention (heavy on network filesystems) gets a
    bounded retry with exponential backoff; exhausting it warns once per path
    (``PlanCacheError`` bookkeeping) instead of silently dropping entries."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    merged = {**load_plan_cache(path), **entries}
    payload = {"version": PLAN_CACHE_VERSION, "entries": merged}
    last: OSError | None = None
    for attempt in range(max(1, retries)):
        tmp = None
        try:
            chaos.maybe_fail("plan_cache_save")
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path) or ".", suffix=".tmp"
            )
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            return
        except OSError as e:  # PlanCacheError is an OSError
            last = e
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            if attempt + 1 < max(1, retries):
                time.sleep(0.01 * (2 ** attempt))
    guard.record_event("plan_cache_save_failed", last)
    guard.warn_once(
        ("plan_cache_save", path),
        f"kron guard: plan-cache save to {path!r} failed after "
        f"{max(1, retries)} attempts ({type(last).__name__}: {last}) — "
        "tuning results not persisted",
    )


def _plan_vmem_legal(plan: KronPlan, prob: KronProblem, batched: bool) -> bool:
    """Would every instruction of the lowered plan (both directions) fit the
    Pallas VMEM budget?  Measured tuning filters its widened sweep with this
    so an XLA wall clock (which ignores tiles) can never cache a plan that
    crashes the Pallas backend later."""
    from ..kernels.emit import (
        PREKRON, VMEM_BUDGET_ELEMS, fused_growth, transposed_growth,
    )

    try:
        prog = lower(plan, prob.ps, prob.qs, batched=batched)
    except Exception:
        return False
    for ins in prog.instrs:
        if ins.kind == PREKRON:
            eff_ps = (math.prod(ins.ps),)
            eff_qs = (math.prod(ins.qs),)
            t_qs = ins.t_qs if ins.t_qs and len(ins.t_qs) == 1 else None
        else:
            eff_ps, eff_qs, t_qs = ins.ps, ins.qs, ins.t_qs
        tb = ins.t_b or 1
        for growth_fn, t_m in (
            (fused_growth, ins.t_m),
            (transposed_growth, ins.t_m_bwd or ins.t_m),
        ):
            if tb * t_m * ins.t_k * growth_fn(eff_ps, eff_qs, t_qs) > (
                VMEM_BUDGET_ELEMS
            ):
                return False
    return True


def _measured_candidates(
    base: KronPlan, prob: KronProblem, batch: int | None
) -> list[KronPlan]:
    """Narrowed candidate set (paper §4.3 structure): the analytic winner
    plus T_M sweeps applied to every stage (forward and backward) and — for
    batched plans — a WIDENED t_b sweep over every divisor of B up to 32 (the
    ROADMAP "batched measured tuning" follow-on: let the wall clock overrule
    the analytic t_b/t_m trade).  Sweep variants that would overflow the
    Pallas VMEM budget are dropped (``_plan_vmem_legal``): the wall clock
    here may be an XLA one that ignores tiles, and a cached Pallas-illegal
    winner would crash a later TPU process."""
    cands = [base]
    for t_m in (4, 8, 16, 32):
        if t_m > prob.m or prob.m % t_m:
            continue
        retile = lambda st: Stage(
            st.factor_ids, st.prekron,
            TileConfig(t_m, st.tiles.t_s, st.tiles.t_q), st.t_qs, st.acc_dtype,
        )
        cands.append(
            KronPlan(
                tuple(retile(s) for s in base.stages),
                tuple(retile(s) for s in (base.bwd_stages or ())) or None,
                base.t_b,
            )
        )
    if batch is not None:
        for plan in list(cands):
            for t_b in (1, 2, 4, 8, 16, 32):
                if t_b > batch or batch % t_b or t_b == plan.t_b:
                    continue
                cands.append(dataclasses.replace(plan, t_b=t_b))
    return [
        c for c in cands
        if c is base or _plan_vmem_legal(c, prob, batch is not None)
    ]


def _measured_plan(
    prob: KronProblem,
    *,
    batch: int | None = None,
    dtype_bytes: int,
    backend: str,
    cache_path: str | None,
    vmem_budget_elems: int = 2 * 1024 * 1024,
    **plan_kwargs,
) -> KronPlan:
    """ONE measured-tuning path for single and batched plans.

    Candidates are ranked by timing the engine's program-driven forward +
    full VJP for each plan — i.e. the EMITTED programs as training actually
    runs them: the lowered forward chain, its ``transpose`` for the input
    cotangent, and the one-kernel factor-gradient stage backward
    (``run_stage_grad``) — so what is ranked is exactly what will run.  The
    winner is persisted in the plan cache together with the candidate set
    that was measured (``"candidates"``) so a later widening of the sweep is
    visible in the cache entry.
    """
    path = cache_path or default_cache_path()
    key = plan_cache_key(
        prob, dtype_bytes, backend,
        vmem_budget_elems=vmem_budget_elems,
        **plan_kwargs,
        **({"batch": batch, "shared_factors": False} if batch is not None else {}),
    )
    entries = load_plan_cache(path)
    hit = entries.get(key)
    if hit is not None:
        telemetry.counter_inc("plan_cache.hit")
        return plan_from_json(hit["plan"])
    telemetry.counter_inc("plan_cache.miss")

    base = make_plan(
        prob, dtype_bytes=dtype_bytes, tune="analytic", backend=backend,
        vmem_budget_elems=vmem_budget_elems, **plan_kwargs,
    )
    if batch is not None:
        base = _batch_tiled(base, prob, batch, vmem_budget_elems, dtype_bytes)
    cands = _measured_candidates(base, prob, batch)

    dtype = {2: jnp.bfloat16, 4: jnp.float32, 8: jnp.float64}.get(
        dtype_bytes, jnp.float32
    )
    lead = () if batch is None else (batch,)
    keys = jax.random.split(jax.random.PRNGKey(0), prob.n + 1)
    x = jax.random.normal(keys[0], (*lead, prob.m, prob.k)).astype(dtype)
    factors = tuple(
        jax.random.normal(kk, (*lead, p, q)).astype(dtype)
        for kk, p, q in zip(keys[1:], prob.ps, prob.qs)
    )
    # Deferred import: engine imports this module at load time.
    from . import engine

    def fn_of_plan(plan):
        op = engine.KronOp(
            prob.ps, prob.qs, backend=backend, plan=plan,
            **({} if batch is None else
               {"batch": batch, "shared_factors": False}),
        )
        f = jax.jit(
            jax.grad(
                lambda x, fs: op(x, fs).sum().astype(jnp.float32),
                argnums=(0, 1),
            )
        )
        return lambda: f(x, factors)

    try:
        with telemetry.span("measure_plan", candidates=len(cands)):
            best, seconds = measure_best(fn_of_plan, cands, warmup=1, iters=3)
    except (RuntimeError, guard.PlanError):
        # No candidate executed (e.g. unsupported backend/dtype combination):
        # fall back to the analytic plan and don't poison the cache.
        return base
    entries[key] = {
        "plan": plan_to_json(best),
        "seconds": seconds,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "candidates": [c.describe() for c in cands],
    }
    save_plan_cache(path, entries)
    return best


def _measured_dist_plan(
    prob: KronProblem,
    *,
    batch: int,
    g_k: int,
    mesh,
    data_axis,
    model_axis: str,
    dtype_bytes: int,
    enable_fusion: bool,
    vmem_budget_elems: int,
    backend: str,
    cache_path: str | None,
    acc_dtype: str | None,
) -> KronPlan:
    """Measured tuning for DISTRIBUTED batched plans: wall-clock rank the
    slab-count candidates by running the real mesh runner (forward + full
    VJP of the emitted round schedule) on the caller's mesh, so slabbed vs
    serial is decided by what the fabric actually does — the analytic ICI
    model cannot see that host-mesh collectives run at memcpy speed (and,
    symmetrically, a real ICI's latency).  The winner is persisted under the
    batched cache key plus a ``;gk=`` component: an APPEND-ONLY extension of
    the key schema, so existing single-host entries keep their keys and old
    cache files load unchanged (distributed entries simply never collide
    with them)."""
    path = cache_path or default_cache_path()
    key = plan_cache_key(
        prob, dtype_bytes, backend,
        enable_fusion=enable_fusion,
        enable_prekron=False,
        vmem_budget_elems=vmem_budget_elems,
        batch=batch,
        shared_factors=False,
        acc_dtype=acc_dtype,
    ) + f";gk={g_k}"
    entries = load_plan_cache(path)
    hit = entries.get(key)
    if hit is not None:
        telemetry.counter_inc("plan_cache.hit")
        return plan_from_json(hit["plan"])
    telemetry.counter_inc("plan_cache.miss")

    cands = _dist_plan_candidates(
        prob, batch, g_k, dtype_bytes=dtype_bytes, enable_fusion=enable_fusion,
        vmem_budget_elems=vmem_budget_elems, backend=backend,
        acc_dtype=acc_dtype,
    )
    fallback = _analytic_dist_plan(
        prob, batch, g_k, dtype_bytes=dtype_bytes, enable_fusion=enable_fusion,
        vmem_budget_elems=vmem_budget_elems, backend=backend,
        acc_dtype=acc_dtype,
    )

    from . import distributed

    g_m = distributed._mesh_size(mesh, data_axis)
    dtype = {2: jnp.bfloat16, 4: jnp.float32, 8: jnp.float64}.get(
        dtype_bytes, jnp.float32
    )
    keys = jax.random.split(jax.random.PRNGKey(0), prob.n + 1)
    x = jax.random.normal(keys[0], (batch, prob.m * g_m, prob.k)).astype(dtype)
    x = distributed.sharded_input_batched(x, mesh, data_axis, model_axis)
    factors = tuple(
        jax.random.normal(kk, (batch, p, q)).astype(dtype)
        for kk, p, q in zip(keys[1:], prob.ps, prob.qs)
    )

    def fn_of_plan(plan):
        f = jax.jit(
            jax.grad(
                lambda x, fs: distributed.run_batched_distributed_rounds(
                    x, fs, mesh,
                    t_b=plan.t_b,
                    data_axis=data_axis,
                    model_axis=model_axis,
                    backend=backend,
                    n_slabs=plan.n_slabs,
                ).sum().astype(jnp.float32),
                argnums=(0, 1),
            )
        )
        return lambda: f(x, factors)

    try:
        with telemetry.span(
            "measure_dist_plan", candidates=len(cands), g_k=g_k
        ):
            best, seconds = measure_best(fn_of_plan, cands, warmup=1, iters=3)
    except (RuntimeError, guard.PlanError):
        # No candidate ran on this mesh (e.g. rows not shardable): analytic
        # fallback, nothing cached.
        return fallback
    entries[key] = {
        "plan": plan_to_json(best),
        "seconds": seconds,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "candidates": [c.describe() for c in cands],
    }
    save_plan_cache(path, entries)
    return best


__all__ = [
    "TileConfig",
    "Stage",
    "KronPlan",
    "make_plan",
    "make_batched_plan",
    "choose_n_slabs",
    "lower",
    "mirror_bwd_stages",
    "tune_sliced",
    "candidate_tiles",
    "predict_seconds",
    "measure_best",
    "vmem_elems",
    "plan_cache_key",
    "plan_to_json",
    "plan_from_json",
    "load_plan_cache",
    "save_plan_cache",
    "default_cache_path",
    "A2A_LATENCY_S",
]
