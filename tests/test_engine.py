"""KronOp: the unified handle-based execution API (engine PR).

Acceptance:
  * a KronOp resolves its plan at construction and matches the dense oracle
    (forward and gradients) on both backends;
  * two ops with the same signature SHARE one plan object, and the engine's
    plan memoization is bounded (no ``maxsize=None`` left on the spine);
  * every legacy ``kron_matmul*`` entry point is a deprecation shim whose
    numerics match the op path exactly (bitwise — same code path);
  * ``.out_shape`` / ``.cost()`` / ``.with_batch`` / ``.with_mesh`` behave
    as the handle API promises;
  * the batched executor runs per-sample PRE-KRONIZATION stages
    (``make_batched_plan(shared_factors=False, enable_prekron=True)``),
    forward and backward.
"""
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KronOp, engine, fastkron
from repro.core.autotune import make_batched_plan, make_plan
from repro.core.engine import kron_op_for
from repro.core.kron import KronProblem, kron_matrix
from repro.core.layers import KronLinear, KronLinearSpec, kron_linear_materialize
from repro.runtime.sharding import make_mesh


def _mk(seed, m, ps, qs, batch=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(ps) + 1)
    lead = () if batch is None else (batch,)
    x = jax.random.normal(keys[0], (*lead, m, math.prod(ps)), jnp.float32)
    fs = tuple(
        jax.random.normal(k, (*lead, p, q), jnp.float32)
        for k, p, q in zip(keys[1:], ps, qs)
    )
    return x, fs


# ---------------------------------------------------------------------------
# The op itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize(
    "m,ps,qs",
    [(8, (4, 4), (4, 4)), (4, (4, 2, 3), (3, 2, 4)), (6, (5, 3), (2, 7))],
)
def test_op_matches_dense_oracle(backend, m, ps, qs):
    x, fs = _mk(0, m, ps, qs)
    op = KronOp(ps, qs, m=m, backend=backend)
    got = op(x, fs)
    want = x @ kron_matrix(list(fs))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert got.shape == op.out_shape(x.shape)

    gx, gf = jax.grad(lambda x, fs: (op(x, fs) ** 2).sum(), argnums=(0, 1))(x, fs)
    gx2, gf2 = jax.grad(
        lambda x, fs: ((x @ kron_matrix(list(fs))) ** 2).sum(), argnums=(0, 1)
    )(x, fs)
    np.testing.assert_allclose(gx, gx2, rtol=1e-4, atol=1e-4)
    for a, b in zip(gf, gf2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_op_resolves_plan_at_construction_and_shares_it():
    """Two ops with one signature hold ONE plan object (bounded module
    memo), and the op's own call path never re-plans."""
    op1 = KronOp((16, 16), (16, 16), m=32)
    op2 = KronOp((16, 16), (16, 16), m=32)
    assert op1 is not op2
    assert op1.plan is op2.plan
    # kron_op_for goes further: same signature -> same op object.
    assert kron_op_for((16, 16), (16, 16)) is kron_op_for((16, 16), (16, 16))


def test_engine_plan_memos_are_bounded():
    """The old unbounded lru_cache(maxsize=None) memos are gone: every cache
    on the engine spine declares a finite maxsize."""
    for cache in (
        engine._resolve_plan,
        engine._resolve_batched_plan,
        engine._kron_fn,
        engine._lowered,
        engine.kron_op_for,
    ):
        assert cache.cache_info().maxsize is not None, cache
    assert not hasattr(fastkron, "_plan_for")
    assert not hasattr(fastkron, "_build_kron_fn")
    assert not hasattr(fastkron, "_batched_plan_for")


def test_op_repeated_calls_hit_op_owned_state():
    """After the first call, the op serves plan+fn from its own tables —
    the module-level plan memo is not consulted again."""
    op = KronOp((4, 4), (4, 4))
    x, fs = _mk(1, 8, (4, 4), (4, 4))
    op(x, fs)
    before = engine._resolve_plan.cache_info()
    for _ in range(3):
        op(x, fs)
    after = engine._resolve_plan.cache_info()
    assert (after.hits, after.misses) == (before.hits, before.misses)


def test_out_shape_and_cost():
    op = KronOp((4, 4), (8, 8), m=16)
    assert op.out_shape((16, 16)) == (16, 64)
    assert op.out_shape((2, 3, 16)) == (2, 3, 64)
    with pytest.raises(ValueError):
        op.out_shape((16, 15))
    c = op.cost()
    assert c.flops == KronProblem(16, (4, 4), (8, 8)).flops
    assert c.comm_elems_per_device == 0 and c.rounds == 0
    # batched per-sample: B independent problems
    opb = op.with_batch(4, shared_factors=False)
    assert opb.cost(m=16).flops == 4 * KronProblem(16, (4, 4), (8, 8)).flops
    assert opb.out_shape((4, 16, 16)) == (4, 16, 64)
    with pytest.raises(ValueError):
        opb.out_shape((3, 16, 16))  # wrong leading batch


def test_with_batch_and_with_mesh_derivations():
    op = KronOp((4, 4), (4, 4))
    opb = op.with_batch(8, shared_factors=False)
    assert (opb.batch, opb.shared_factors) == (8, False)
    assert (opb.ps, opb.qs) == (op.ps, op.qs)
    mesh = make_mesh((1, 1), ("data", "model"))
    opd = op.with_mesh(mesh)
    assert opd.mesh is mesh and opd.rounds is not None
    assert opd.cost(m=8).rounds == len(opd.rounds)
    # infeasible round schedule fails AT CONSTRUCTION (fail fast), not at call
    if jax.device_count() >= 2:
        bad = make_mesh((1, jax.device_count()), ("data", "model"))
        ps = (3, 3)  # prod(Q)=9 never divisible by an even G_K
        if jax.device_count() % 2 == 0:
            with pytest.raises(ValueError):
                KronOp(ps, ps, mesh=bad)


def test_mesh_op_on_trivial_mesh_matches_local():
    """The mesh spine is the same math: a 1x1 mesh reproduces the local op
    bit-for-bit shapes/numerics (collectives degenerate away)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    x, fs = _mk(2, 8, (4, 4), (4, 4))
    op = KronOp((4, 4), (4, 4), mesh=mesh)
    got = op(x, fs)
    want = KronOp((4, 4), (4, 4))(x, fs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Deprecation shims (satellite): warn once, numerics identical
# ---------------------------------------------------------------------------


def test_legacy_shims_warn_once_and_match_op_exactly():
    x, fs = _mk(3, 8, (4, 4), (4, 4))
    xb, fb = _mk(4, 8, (4, 4), (4, 4), batch=4)
    op = KronOp((4, 4), (4, 4))
    opb = op.with_batch(4, shared_factors=False)

    engine._DEPRECATION_WARNED.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        y1 = fastkron.kron_matmul(x, fs)
        y1_again = fastkron.kron_matmul(x, fs)
        y2 = fastkron.kron_matmul_batched(xb, fb, shared_factors=False)
    dep = [d for d in w if issubclass(d.category, DeprecationWarning)]
    names = [str(d.message).split(" ", 1)[0] for d in dep]
    # one warning per entry point, not per call
    assert names.count("kron_matmul") == 1, names
    assert names.count("kron_matmul_batched") == 1, names
    assert all("KronOp" in str(d.message) for d in dep)
    # the shim IS the op path: bitwise-identical results
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(op(x, fs)))
    np.testing.assert_array_equal(np.asarray(y1_again), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(opb(xb, fb)))


def test_distributed_shims_warn_once():
    mesh = make_mesh((1, 1), ("data", "model"))
    from repro.core import distributed

    x, fs = _mk(5, 8, (4, 4), (4, 4))
    engine._DEPRECATION_WARNED.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        y = distributed.kron_matmul_distributed(x, fs, mesh)
        distributed.kron_matmul_distributed(x, fs, mesh)
    dep = [d for d in w if issubclass(d.category, DeprecationWarning)]
    assert len(dep) == 1 and "kron_matmul_distributed" in str(dep[0].message)
    want = KronOp((4, 4), (4, 4), mesh=mesh)(x, fs)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


# ---------------------------------------------------------------------------
# Per-sample pre-kronization (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_batched_per_sample_prekron_stage(backend):
    """make_batched_plan(shared_factors=False, enable_prekron=True) emits
    prekron stages and the batched executor runs them: forward AND full
    gradients match the looped dense reference."""
    b, m, ps, qs = 4, 8, (4, 4, 4), (4, 4, 4)
    plan = make_batched_plan(
        KronProblem(m, ps, qs), b, shared_factors=False, enable_prekron=True,
        prekron_max_p=4,
    )
    assert any(st.prekron for st in plan.stages), plan.describe()
    x, fb = _mk(6, m, ps, qs, batch=b)
    op = KronOp(ps, qs, batch=b, shared_factors=False, backend=backend, plan=plan)

    def loss(x, fb):
        return (op(x, fb) ** 2).sum()

    def loss_ref(x, fb):
        t = 0.0
        for i in range(b):
            t = t + ((x[i] @ kron_matrix([f[i] for f in fb])) ** 2).sum()
        return t

    np.testing.assert_allclose(
        np.asarray(op(x, fb)),
        np.stack([np.asarray(x[i] @ kron_matrix([f[i] for f in fb]))
                  for i in range(b)]),
        rtol=1e-4, atol=1e-4,
    )
    got = jax.grad(loss, argnums=(0, 1))(x, fb)
    want = jax.grad(loss_ref, argnums=(0, 1))(x, fb)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-3)
    for a, wf in zip(got[1], want[1]):
        np.testing.assert_allclose(a, wf, rtol=1e-4, atol=1e-3)
    # dx-only (symbolic-zeros) path through the prekron transposed branch
    gx = jax.grad(lambda x: loss(x, fb))(x)
    np.testing.assert_allclose(gx, want[0], rtol=1e-4, atol=1e-3)


def test_batched_plan_prekron_passthrough():
    """The per-sample planner honors enable_prekron instead of hard-coding
    it off (the executor now has the per-sample explicit-kron stage)."""
    prob = KronProblem(8, (4, 4, 4), (4, 4, 4))
    off = make_batched_plan(prob, 4, shared_factors=False)
    on = make_batched_plan(
        prob, 4, shared_factors=False, enable_prekron=True, prekron_max_p=4
    )
    assert not any(st.prekron for st in off.stages)
    assert any(st.prekron for st in on.stages)


# ---------------------------------------------------------------------------
# KronLinear holds its op
# ---------------------------------------------------------------------------


def test_kron_linear_module_holds_op():
    spec = KronLinearSpec((4, 4), (4, 4), use_bias=True)
    lin = KronLinear(jax.random.PRNGKey(0), spec)
    # plan built at init and shared with every other op of this signature
    assert lin.op.plan is kron_op_for(spec.ps, spec.qs).plan
    x = jax.random.normal(jax.random.PRNGKey(1), (8, spec.d_in))
    w = kron_linear_materialize(lin.params)
    np.testing.assert_allclose(
        lin(x), x @ w + lin.params["bias"], rtol=1e-4, atol=1e-4
    )
    # batches collapse into the op's row axis — same module, any rank
    xb = jax.random.normal(jax.random.PRNGKey(2), (2, 8, spec.d_in))
    np.testing.assert_allclose(
        lin(xb), xb @ w + lin.params["bias"], rtol=1e-4, atol=1e-4
    )


def test_prebuild_kron_ops_warms_the_shared_plan_memo():
    """Serving prebuild resolves the (batch*seq_len)-row plan up front: the
    layer apply's own plan lookup must be a HIT, not a fresh tile search."""
    from dataclasses import dataclass

    from repro.train.steps import prebuild_kron_ops

    @dataclass
    class Cfg:
        kron_ffn: bool = True
        kron_factors: int = 2
        d_model: int = 64
        d_ff: int = 256
        dtype: str = "float32"

    engine._resolve_plan.cache_clear()
    ops = prebuild_kron_ops(Cfg(), batch=4, seq_len=8)
    assert len(ops) == 2
    assert engine._resolve_plan.cache_info().misses >= 2  # plans built NOW
    before = engine._resolve_plan.cache_info().misses
    # what kron_linear_apply resolves at trace time for (4, 8, d) inputs:
    for op in ops:
        engine._resolve_plan(
            4 * 8, op.ps, op.qs, 4, "auto", engine._auto_prekron(),
            "analytic", None,
        )
    assert engine._resolve_plan.cache_info().misses == before  # all hits


def test_with_batch_drops_the_row_hint():
    """m means total rows on a single op but rows-per-sample on a batched
    op — the derivation must not eagerly plan for the wrong shape."""
    op = KronOp((4, 4), (4, 4), m=32)
    opb = op.with_batch(4, shared_factors=False)
    assert opb._m is None
    assert not opb._plans  # nothing eagerly resolved for a bogus shape


def test_op_describe_smoke():
    op = KronOp((4, 4), (4, 4), batch=8, shared_factors=False)
    d = op.describe()
    assert "KronOp" in d and "per-sample" in d and "t_b" in d
