"""StageProgram IR + unified emitter (the one-kernel-template refactor).

Acceptance pins:
  * ``transpose`` is mechanical (involution on structure) and
    ``emit(transpose(prog))`` is the x-cotangent of ``emit(prog)``;
  * ``autotune.lower`` lowers any KronPlan into a program whose emission
    matches the dense oracle on BOTH backends;
  * per-stage heterogeneity works end to end: a mixed-shape ``ps=(8,16,32)``
    chain with per-stage ``acc_dtype`` flows plan -> program -> emitter ->
    VJP on xla and pallas-interpret.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.autotune import lower, make_plan
from repro.core.engine import KronOp
from repro.core.kron import KronProblem, kron_matrix
from repro.kernels import emit
from repro.runtime import telemetry

jax.config.update("jax_enable_x64", True)


def _mk(seed, m, ps, qs, batch=None, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(ps) + 1)
    lead = () if batch is None else (batch,)
    x = jax.random.normal(keys[0], (*lead, m, math.prod(ps))).astype(dtype)
    fs = tuple(
        jax.random.normal(k, (*lead, p, q)).astype(dtype)
        for k, p, q in zip(keys[1:], ps, qs)
    )
    return x, fs


# ---------------------------------------------------------------------------
# IR structure
# ---------------------------------------------------------------------------


def test_instr_kind_direction_consistency():
    i = emit.StageInstr(kind=emit.MULTIPLY, ps=(4,), qs=(4,))
    assert i.direction == "fwd"
    t = i.transpose()
    assert t.kind == emit.TRANSPOSED_MULTIPLY and t.direction == "bwd"
    assert t.transpose().kind == emit.MULTIPLY
    pk = emit.StageInstr(kind=emit.PREKRON, ps=(2, 2), qs=(2, 2))
    assert pk.transpose().kind == emit.PREKRON
    assert pk.transpose().direction == "bwd"
    with pytest.raises(ValueError):
        emit.StageInstr(kind="frobnicate", ps=(4,), qs=(4,))
    with pytest.raises(ValueError):
        emit.StageInstr(kind=emit.MULTIPLY, ps=(4,), qs=(4, 4))


def test_transpose_swaps_tuned_bwd_tile():
    i = emit.StageInstr(
        kind=emit.MULTIPLY, ps=(4, 4), qs=(4, 4), t_m=8, t_m_bwd=2
    )
    t = i.transpose()
    assert (t.t_m, t.t_m_bwd) == (2, 8)
    assert t.transpose().t_m == 8  # involution restores the forward tile


def test_program_covers_factors_exactly_once():
    mk = lambda ids: emit.StageInstr(
        kind=emit.MULTIPLY, ps=(4,) * len(ids), qs=(4,) * len(ids),
        factor_ids=ids,
    )
    emit.StageProgram((mk((0, 1)), mk((2,))), 3)  # ok
    with pytest.raises(ValueError):
        emit.StageProgram((mk((0, 1)),), 3)  # missing factor 2
    with pytest.raises(ValueError):
        emit.StageProgram((mk((0,)), mk((0,))), 1)  # duplicate


def test_transpose_reverses_instruction_order():
    prob = KronProblem(8, (4, 2, 3), (3, 2, 4))
    plan = make_plan(prob, enable_prekron=False)
    prog = lower(plan, prob.ps, prob.qs)
    t = emit.transpose(prog)
    assert [i.factor_ids for i in t.instrs] == [
        i.factor_ids for i in reversed(prog.instrs)
    ]
    assert all(i.direction == "bwd" for i in t.instrs)


def test_lower_carries_plan_fields():
    prob = KronProblem(8, (4, 4, 4), (4, 4, 4))
    plan = make_plan(prob, enable_prekron=False)
    prog = lower(plan, prob.ps, prob.qs)
    assert prog.n_factors == 3
    assert not prog.batched
    for st, ins in zip(plan.stages, prog.instrs):
        assert ins.factor_ids == st.factor_ids
        assert ins.t_m == st.tiles.t_m
        assert ins.t_k == st.tiles.t_s * math.prod(ins.ps)
        assert ins.t_qs == st.t_qs
    bprog = lower(plan, prob.ps, prob.qs, batched=True)
    assert all(i.t_b == plan.t_b for i in bprog.instrs)


# ---------------------------------------------------------------------------
# Emission correctness + transpose-is-vjp
# ---------------------------------------------------------------------------


CHAINS = [
    (8, (4, 4), (4, 4)),
    (4, (4, 2, 3), (3, 2, 4)),
    (8, (8, 16, 32), (8, 16, 32)),     # the mixed-shape acceptance chain
    (6, (5, 3), (2, 7)),
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("m,ps,qs", CHAINS)
def test_emitted_program_matches_dense_oracle(backend, m, ps, qs):
    x, fs = _mk(0, m, ps, qs, dtype=jnp.float64)
    plan = make_plan(KronProblem(m, ps, qs), enable_prekron=False)
    prog = lower(plan, ps, qs)
    got = emit.emit(prog, backend=backend)(x, fs)
    np.testing.assert_allclose(
        got, x @ kron_matrix(list(fs)), rtol=1e-9, atol=1e-9
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("m,ps,qs", CHAINS)
def test_transpose_program_is_vjp(backend, m, ps, qs):
    """emit(transpose(prog)) == the jax.vjp x-cotangent of emit(prog).

    The vjp reference differentiates the XLA emission (interpret-mode
    pallas_call is not linearizable under jax.vjp — the engine never
    differentiates THROUGH kernels, it runs transposed programs); the
    transposed program is then emitted on BOTH backends against it."""
    x, fs = _mk(1, m, ps, qs, dtype=jnp.float64)
    plan = make_plan(KronProblem(m, ps, qs), enable_prekron=False)
    prog = lower(plan, ps, qs)
    y, vjp = jax.vjp(lambda x: emit.emit(prog, backend="xla")(x, fs), x)
    dy = jax.random.normal(jax.random.PRNGKey(2), y.shape, jnp.float64)
    (want,) = vjp(dy)
    got = emit.emit(emit.transpose(prog), backend=backend)(dy, fs)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_batched_transpose_program_is_vjp(backend):
    b, m, ps, qs = 4, 4, (4, 8), (8, 4)
    x, fs = _mk(3, m, ps, qs, batch=b)
    plan = autotune.make_batched_plan(
        KronProblem(m, ps, qs), b, shared_factors=False
    )
    prog = lower(plan, ps, qs, batched=True)
    y, vjp = jax.vjp(lambda x: emit.emit(prog, backend="xla")(x, fs), x)
    dy = jax.random.normal(jax.random.PRNGKey(4), y.shape, jnp.float32)
    (want,) = vjp(dy)
    got = emit.emit(emit.transpose(prog), backend=backend)(dy, fs)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prekron_program_round_trip(backend):
    m, ps, qs = 4, (2, 3, 2), (3, 2, 2)
    x, fs = _mk(5, m, ps, qs, dtype=jnp.float64)
    plan = make_plan(
        KronProblem(m, ps, qs), enable_prekron=True, prekron_max_p=4
    )
    assert any(st.prekron for st in plan.stages), plan.describe()
    prog = lower(plan, ps, qs)
    assert any(i.kind == emit.PREKRON for i in prog.instrs)
    fwd = emit.emit(prog, backend=backend)
    np.testing.assert_allclose(
        fwd(x, fs), x @ kron_matrix(list(fs)), rtol=1e-9, atol=1e-9
    )
    y, vjp = jax.vjp(lambda x: emit.emit(prog, backend="xla")(x, fs), x)
    dy = jnp.ones_like(y)
    (want,) = vjp(dy)
    got = emit.emit(emit.transpose(prog), backend=backend)(dy, fs)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# Mixed per-stage (p, q) + acc_dtype end to end (the proof scenario)
# ---------------------------------------------------------------------------


def _per_stage_acc_plan(m, ps, qs):
    """One stage per factor with a DIFFERENT acc dtype on each stage."""
    plan = make_plan(
        KronProblem(m, ps, qs), enable_prekron=False, enable_fusion=False
    )
    accs = ["float32", "float64", None]
    stages = tuple(
        dataclasses.replace(st, acc_dtype=accs[i % 3])
        for i, st in enumerate(plan.stages)
    )
    bwd = tuple(
        dataclasses.replace(st, acc_dtype=accs[(len(stages) - 1 - i) % 3])
        for i, st in enumerate(plan.bwd_stages)
    )
    return autotune.KronPlan(stages, bwd, plan.t_b)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_mixed_shape_mixed_acc_chain_end_to_end(backend):
    """ps=(8,16,32) with per-stage acc_dtype through the WHOLE stack:
    plan -> program -> emitter -> VJP, forward and full gradients."""
    m, ps, qs = 4, (8, 16, 32), (8, 16, 32)
    plan = _per_stage_acc_plan(m, ps, qs)
    prog = lower(plan, ps, qs)
    assert {i.acc_dtype for i in prog.instrs} == {"float32", "float64", None}
    x, fs = _mk(7, m, ps, qs)
    op = KronOp(ps, qs, m=m, backend=backend, plan=plan)
    got = op(x, fs)
    want = x @ kron_matrix(list(fs))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    gx, gf = jax.grad(lambda x, fs: (op(x, fs) ** 2).sum(), argnums=(0, 1))(x, fs)
    gx2, gf2 = jax.grad(
        lambda x, fs: ((x @ kron_matrix(list(fs))) ** 2).sum(), argnums=(0, 1)
    )(x, fs)
    np.testing.assert_allclose(gx, gx2, rtol=1e-2, atol=1e-2)
    for a, b in zip(gf, gf2):
        np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-2)


def test_make_plan_acc_dtype_stamps_stages_and_caches_separately():
    prob = KronProblem(8, (4, 4), (4, 4))
    plan = make_plan(prob, acc_dtype="float64", enable_prekron=False)
    assert all(st.acc_dtype == "float64" for st in plan.stages)
    assert all(st.acc_dtype == "float64" for st in plan.bwd_stages)
    # plan-cache keys must distinguish acc policies (and default stays stable)
    k_default = autotune.plan_cache_key(prob, 4, "xla")
    k_acc = autotune.plan_cache_key(prob, 4, "xla", acc_dtype="float64")
    assert k_default != k_acc and "acc=" not in k_default
    # JSON round-trip keeps the per-stage policy
    assert autotune.plan_from_json(autotune.plan_to_json(plan)) == plan


def test_mixed_shape_batched_per_sample(backend="xla"):
    """The same mixed-shape chain through the batched per-sample spine."""
    b, m, ps, qs = 2, 4, (8, 16, 32), (4, 8, 16)
    x, fs = _mk(8, m, ps, qs, batch=b)
    op = KronOp(ps, qs, batch=b, shared_factors=False, backend=backend)
    got = op(x, fs)
    want = np.stack(
        [np.asarray(x[i] @ kron_matrix([f[i] for f in fs])) for i in range(b)]
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Unified executor plumbing
# ---------------------------------------------------------------------------


def test_lower_carries_single_stage_q_tile_for_huge_q():
    """Single-multiply stages keep their tuned Q-tile through lowering: a
    huge-Q factor whose full-Q growth would fail the chain template's VMEM
    check must lower to an emittable instruction (the kron_sliced kernel's
    t_q semantics, now expressed as a length-1 t_qs)."""
    m, ps, qs = 64, (2, 2), (32768, 32768)
    plan = make_plan(KronProblem(m, ps, qs), enable_fusion=False,
                     enable_prekron=False)
    prog = lower(plan, ps, qs)
    assert any(i.t_qs is not None for i in prog.instrs), prog.describe()
    for ins in prog.instrs:
        growth = emit.fused_growth(ins.ps, ins.qs, ins.t_qs)
        assert ins.t_m * ins.t_k * growth <= emit.VMEM_BUDGET_ELEMS, (
            prog.describe()
        )
    # Numeric pin of the length-1-t_qs chain template (the path lowering
    # now routes those stages through) at a size cheap enough to interpret.
    x, fs = _mk(10, 4, (4,), (64,), dtype=jnp.float64)
    instr = emit.StageInstr(
        kind=emit.MULTIPLY, ps=(4,), qs=(64,), t_m=2, t_k=8, t_qs=(16,)
    )
    got = emit.run_stage(x, tuple(reversed(fs)), instr, backend="pallas")
    np.testing.assert_allclose(
        got, x @ kron_matrix(list(fs)), rtol=1e-9, atol=1e-9
    )


def test_plan_growth_repair_keeps_fused_stages_emittable():
    """The planner's fusion grouping must never emit a stage whose minimal
    tile exceeds the VMEM budget: the first factor used to be admitted with
    full Q unchecked, blowing the early-prefix growth (review finding)."""
    for ps, qs in [((2048, 2), (2048, 2048)), ((2, 2), (2048, 2048))]:
        prob = KronProblem(8, ps, qs)
        plan = make_plan(prob, enable_prekron=False)
        prog = lower(plan, ps, qs)
        for ins in prog.instrs:
            if len(ins.ps) <= 1:
                continue
            growth = emit.fused_growth(ins.ps, ins.qs, ins.t_qs)
            assert ins.t_m * ins.t_k * growth <= emit.VMEM_BUDGET_ELEMS, (
                prog.describe()
            )


def test_unbatched_is_batch_of_one_on_pallas():
    """t_b=None and an explicit B=1 batch emit the same numbers — batch is a
    grid axis, not a code path."""
    m, ps, qs = 4, (4, 4), (4, 4)
    x, fs = _mk(9, m, ps, qs)
    instr = emit.StageInstr(kind=emit.MULTIPLY, ps=ps, qs=qs, t_m=2, t_k=16)
    single = emit.run_stage(x, tuple(reversed(fs)), instr, backend="pallas")
    batched = emit.run_stage(
        x[None], tuple(f[None] for f in reversed(fs)),
        dataclasses.replace(instr, t_b=1), backend="pallas",
    )
    np.testing.assert_array_equal(np.asarray(single), np.asarray(batched[0]))


def test_run_stage_raises_on_vmem_overflow():
    x = jnp.zeros((8, 1 << 14), jnp.float32)
    f = jnp.zeros((2, 2), jnp.float32)
    instr = emit.StageInstr(
        kind=emit.MULTIPLY, ps=(2, 2), qs=(2, 2), t_m=8, t_k=1 << 14
    )
    with pytest.raises(ValueError):
        emit.run_stage(
            x, (f, f), instr, backend="pallas", vmem_budget_elems=1024
        )


def test_run_program_validates_factor_count():
    prog = emit.StageProgram(
        (emit.StageInstr(kind=emit.MULTIPLY, ps=(4,), qs=(4,), factor_ids=(0,)),),
        1,
    )
    with pytest.raises(ValueError):
        emit.run_program(jnp.zeros((2, 4)), (jnp.zeros((4, 4)),) * 2, prog)


# ---------------------------------------------------------------------------
# Compiled-kernel legality: block shapes Mosaic accepts, padded VMEM, routing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,ps,t_m,t_k,t_qs,itemsize,legal",
    [
        (16, 512, (8, 8, 8), 16, 512, None, 4, True),  # full extents
        (16, 512, (8,), 4, 512, None, 4, False),  # rows: not a sublane multiple
        (32, 512, (8,), 16, 512, None, 2, True),  # bf16 sublane tile is 16
        (32, 512, (8,), 8, 512, None, 2, False),
        (16, 4096, (16,), 16, 16 * 128, None, 4, True),  # 128 slices per block
        (16, 4096, (16,), 16, 16 * 64, None, 4, False),  # 64 slices per block
        (16, 512, (8,), 16, 512, (4,), 4, False),  # Q-tiled output block
    ],
)
def test_tpu_block_error_legality(m, k, ps, t_m, t_k, t_qs, itemsize, legal):
    why = emit.tpu_block_error(1, m, k, ps, ps, t_m, t_k, t_qs, itemsize)
    assert (why is None) == legal, why


def test_legal_tiles_are_legal_and_fit_padded_vmem():
    """qwen3-4b kron_ffn up-projection factors at batch 4 x seq 512."""
    ps, qs = (40, 64), (76, 128)
    for grad in (False, True):
        t_b, t_m, t_k = emit.legal_tiles(
            "fwd", 1, 2048, 2560, ps, qs, t_b=1, t_m=32, itemsize=4, grad=grad
        )
        assert emit.tpu_block_error(1, 2048, 2560, ps, qs, t_m, t_k, None, 4) is None
        need = emit.chain_vmem_bytes(
            t_b, t_m, t_k, ps, qs, direction="fwd", flat=t_k == 2560,
            in_bytes=4, out_bytes=4, grad=grad,
        )
        assert need <= emit.VMEM_BUDGET_ELEMS * 4
    assert emit.legal_tiles(
        "fwd", 1, 2048, 2560, ps, qs, t_b=1, t_m=32, itemsize=4,
        budget_bytes=1 << 16,
    ) is None


def test_vmem_model_counts_lane_padding():
    """A (t_m, 256, 256, 1)-shaped block pads its trailing 1 to 128 lanes."""
    assert emit._tiled_bytes((8, 256, 1), 4) == 8 * 256 * 128 * 4
    assert emit._tiled_bytes((4, 100), 2) == 16 * 128 * 2


def test_no_legal_tiling_routes_stage_to_xla_in_describe():
    """Table 4 row 22: M=1526 has no multiple-of-8 divisor, and a full-M
    block of the (16,16) prekron stage needs ~119 MiB of VMEM, over the
    budget — the compiled emitter routes it to XLA, decided from shapes and
    shown by ``describe()``."""
    instr = emit.StageInstr(emit.PREKRON, (4, 4), (4, 4), (0, 1), t_m=1526)
    assert emit.stage_tiles(instr, (1526, 4096), jnp.float32) is None
    assert emit.stage_tiles(instr, (1526, 4096), jnp.float32, grad=True) is None
    op = KronOp((4,) * 6, (4,) * 6, m=1526, backend="pallas")
    assert "xla" in op.describe().split(":: exec[")[1]
    assert all(ex != "xla" for _, ex, _ in
               KronOp((8,) * 3, (8,) * 3, m=16, backend="pallas").stage_executors())


# (M, t_m, dtype, view): 8-row groups, a block of all of fewer than 8 rows,
# and M = 20 with no 8-row groups (the view XLA relayouts).
Y_VIEW_CASES = [
    (16, 8, jnp.float32, "bitcast"),
    (4, 4, jnp.float32, "bitcast"),
    (20, 20, jnp.float32, "relayout"),
    (16, 16, jnp.bfloat16, "bitcast"),
]


@pytest.mark.parametrize("kernel", ["fwd", "bwd", "grad"])
@pytest.mark.parametrize(
    "m,t_m,dtype,view", Y_VIEW_CASES,
    ids=["m16", "m4", "m20", "m16_bf16"],
)
def test_k_tiled_y_view_matches_xla_executor(kernel, m, t_m, dtype, view):
    """K-tiled blocks (K = 4096, 2048-column tiles of one 16x16 factor,
    ts = 128) read and write the y-side array through the view
    ``y_view_rows`` picks; every kernel matches the XLA executor, and the
    trace-time choice is counted."""
    x = _mk(3, m, (16,) * 3, (16,) * 3)[0].astype(dtype)
    g = _mk(5, m, (16,) * 3, (16,) * 3)[0].astype(dtype)
    f = _mk(4, 16, (16,), (16,))[1][0].astype(dtype)
    kw = dict(t_b=1, t_m=t_m, t_k=2048, interpret=True)
    assert emit.y_view_rows(m, t_m, jnp.dtype(dtype).itemsize) == (
        min(m, 8) if view == "bitcast" else None
    )
    tol = 1e-4 if dtype == jnp.float32 else 1e-2
    tol = dict(rtol=tol, atol=tol)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    telemetry.configure()
    try:
        if kernel == "grad":
            emit.grad_pallas.clear_cache()
            dx, (df,) = emit.grad_pallas(x[None], g[None], f[None], **kw)
            dx_ref, (df_ref,) = emit._grad_xla(x, g, (f,))
            np.testing.assert_allclose(f32(dx[0]), f32(dx_ref), **tol)
            np.testing.assert_allclose(f32(df[0]), f32(df_ref), **tol)
        else:
            emit.chain_pallas.clear_cache()
            y_in = x if kernel == "fwd" else g
            got = emit.chain_pallas(y_in[None], f[None], direction=kernel, **kw)[0]
            want = emit._chain_xla(y_in, (f,), direction=kernel)
            np.testing.assert_allclose(f32(got), f32(want), **tol)
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.reset()
    other = "relayout" if view == "bitcast" else "bitcast"
    assert counters.get(f"emit.y_view.{view}", 0) >= 1
    assert counters.get(f"emit.y_view.{other}", 0) == 0


def test_stage_view_pins_gp26_bitcast_and_m20_relayout():
    """Table 4 row 26 (M = 16, six 16x16 factors) runs three K-tiled
    prekron stages through the bitcast view, forward and backward; at
    M = 20 the same stages keep the relayouted view.  Whole-K stages name
    no view."""
    gp26 = KronOp((16,) * 6, (16,) * 6, m=16, backend="pallas", enable_prekron=True)
    execs = gp26.stage_executors()
    assert len(execs) == 3
    assert all(
        fwd.endswith(":bitcast") and grad.endswith(":bitcast")
        for _, fwd, grad in execs
    ), execs
    assert ":bitcast" in gp26.describe().split(":: exec[")[1]
    m20 = KronOp((16,) * 4, (16,) * 4, m=20, backend="pallas", enable_prekron=True)
    assert all(
        fwd.endswith(":relayout") and grad.endswith(":relayout")
        for _, fwd, grad in m20.stage_executors()
    )
    instr = emit.StageInstr(emit.PREKRON, (16, 16), (16, 16), (0, 1), t_m=8)
    assert emit.stage_view(instr, (16, 16 ** 6), jnp.float32) == "bitcast"
    assert emit.stage_view(instr, (16, 16 ** 3), jnp.float32) is None  # whole K


@pytest.mark.parametrize(
    "s,p,q,lanes", [(4, 8, 8, 16), (1, 40, 76, 32), (6, 4, 16, 128)]
)
def test_slice_batched_step_matches_lane_merged_step(s, p, q, lanes):
    """Compiled, a factor step whose lanes are not a multiple of 128 runs as
    a GEMM batched over its slices; interpreted, every step folds the slices
    into the lanes.  Both forms give the same step, transposed step and
    factor gradient."""
    kw, kf, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(kw, (s * p, lanes), jnp.float32)
    f = jax.random.normal(kf, (p, q), jnp.float32)
    g = jax.random.normal(kg, (q * s, lanes), jnp.float32)
    tol = dict(rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        emit._step(w, f, jnp.float32, False),
        emit._step(w, f, jnp.float32, True), **tol,
    )
    df_b, out_b = emit._step_t(g, f, jnp.float32, False, u=w)
    df_m, out_m = emit._step_t(g, f, jnp.float32, True, u=w)
    np.testing.assert_allclose(out_b, out_m, **tol)
    np.testing.assert_allclose(df_b, df_m, **tol)
