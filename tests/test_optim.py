"""Kron-factored Shampoo optimizer: correctness, degradation, telemetry.

The contracts pinned here (docs/optim.md):

* identity roots reproduce the grafted-AdamW step EXACTLY — the shared
  fallback target for warmup, stale intervals, and failed refreshes;
* the shape-grouped batched KronOp apply is bitwise identical to the
  looped per-layer reference (tiles never split the contraction dim);
* a layer's preconditioned update is invariant to the other members of
  its shape group (ordering, company) — per-sample factors really are
  per-sample;
* state round-trips through the checkpoint manager;
* a chaos-injected ``root_refresh`` fault degrades the layer to grafted
  AdamW for the interval and lands in guard health — never crashes;
* telemetry off adds zero compiled HLO to the optimizer path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.optim import adamw
from repro.optim import shampoo as sh
from repro.optim.adamw import OptConfig, opt_init, opt_update
from repro.optim.shampoo import ShampooConfig
from repro.runtime import chaos, guard, telemetry
from repro.runtime.hlo_analysis import strip_source_locations


@pytest.fixture(autouse=True)
def _fresh_state():
    guard.reset_health()
    telemetry.reset()
    yield
    guard.reset_health()
    telemetry.reset()


def _params(seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 6)
    return {
        "embed": jax.random.normal(ks[0], (48, 16)) * 0.1,
        "stack": {
            "w1": jax.random.normal(ks[1], (2, 16, 32)) * 0.1,
            "w2": jax.random.normal(ks[2], (2, 32, 16)) * 0.1,
            "wq": jax.random.normal(ks[3], (2, 16, 16)) * 0.1,
            "ln": jnp.ones((2, 16)),  # stacked norm: (S, d) -> AdamW path
        },
        "head": jax.random.normal(ks[4], (16, 32)) * 0.1,
        "bias": jnp.zeros((16,)),
    }


def _grads(params, seed=1):
    leaves, treedef = jax.tree.flatten(params)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(
        treedef, [jax.random.normal(k, l.shape) for k, l in zip(ks, leaves)]
    )


# ---------------------------------------------------------------------------
# Eligibility / grouping
# ---------------------------------------------------------------------------


def test_rank_shortlist():
    cfg = ShampooConfig()
    groups = sh.shape_groups(_params(), cfg)
    member_paths = {p for members in groups.values() for p, _ in members}
    # 1-D bias and the (S, d) stacked norm fall back to AdamW
    assert "bias" not in member_paths
    assert "stack/ln" not in member_paths
    # stacked 3-D leaves contribute S samples to their group
    assert ("head", 1) in groups[(16, 32)]
    assert ("stack/w1", 2) in groups[(16, 32)]
    # vocab-sized dims beyond the shortlist fall back too
    small = dataclasses.replace(cfg, max_precond_dim=20)
    g2 = sh.shape_groups(_params(), small)
    assert "embed" not in {p for m in g2.values() for p, _ in m}


def test_prebuild_includes_optimizer_ops():
    from repro.configs import get_config
    from repro.models.config import reduced
    from repro.train.steps import prebuild_kron_ops

    cfg = reduced(
        get_config("qwen3_4b"), n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, vocab=64,
        vocab_pad_multiple=32, dtype="float32",
    )
    ops = prebuild_kron_ops(cfg, opt_cfg=ShampooConfig())
    assert ops, "shampoo opt_cfg must prewarm the shape-group ops"
    assert all(op.batch is not None and not op.shared_factors for op in ops)
    assert prebuild_kron_ops(cfg, opt_cfg=OptConfig()) == ()


# ---------------------------------------------------------------------------
# Correctness: identity roots == grafted AdamW, batched == looped == dense
# ---------------------------------------------------------------------------


def test_identity_roots_match_adamw_exactly():
    """Fresh roots are identity -> the whole step IS the AdamW step, for
    eligible and ineligible leaves alike (the degradation target)."""
    params, grads = _params(), _grads(_params())
    acfg = OptConfig()
    scfg = ShampooConfig(precond_every=50)
    ast = opt_init(params, acfg)
    sst = sh.shampoo_init(params, scfg)
    # step 2: past the step==1 refresh, roots still identity
    ast["step"] = jnp.asarray(1, jnp.int32)
    sst["step"] = jnp.asarray(1, jnp.int32)
    ap, ast2, am = opt_update(grads, ast, params, acfg)
    sp, sst2, sm = sh.shampoo_update(grads, sst, params, scfg)
    for a, s_ in zip(jax.tree.leaves(ap), jax.tree.leaves(sp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(s_))
    for k in ("m", "v"):
        for a, s_ in zip(jax.tree.leaves(ast2[k]), jax.tree.leaves(sst2[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(s_))
    assert float(am["grad_norm"]) == float(sm["grad_norm"])


def _refreshed_state(params, grads, cfg):
    """One step from init: the step==1 refresh computes real roots."""
    st = sh.shampoo_init(params, cfg)
    _, st1, _ = sh.shampoo_update(grads, st, params, cfg)
    return st1


def test_batched_apply_bitwise_equals_looped():
    params = _params()
    cfg = ShampooConfig()
    kron = _refreshed_state(params, _grads(params), cfg)["kron"]
    ups = {
        path: jax.random.normal(
            jax.random.PRNGKey(hash(path) % 2**31),
            (
                e["ok"].shape[0],
                e["lroot"].shape[-1],
                e["rroot"].shape[-1],
            ),
        )
        for path, e in kron.items()
    }
    yb = sh.precondition(ups, kron)
    yl = sh.precondition(ups, kron, looped=True)
    assert set(yb) == set(yl)
    for path in yb:
        np.testing.assert_array_equal(np.asarray(yb[path]), np.asarray(yl[path]))


def test_precondition_matches_dense_reference():
    """The KronOp apply computes Lroot^T u Rroot per layer."""
    params = _params()
    cfg = ShampooConfig()
    kron = _refreshed_state(params, _grads(params), cfg)["kron"]
    ups = {
        path: jnp.ones(
            (e["ok"].shape[0], e["lroot"].shape[-1], e["rroot"].shape[-1])
        )
        for path, e in kron.items()
    }
    out = sh.precondition(ups, kron)
    for path, e in kron.items():
        ref = jnp.einsum(
            "spk,spq,sqj->skj", e["lroot"], ups[path], e["rroot"]
        )
        np.testing.assert_allclose(
            np.asarray(out[path]), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_update_invariant_to_group_ordering():
    """Per-sample factors: a layer's preconditioned update must not depend
    on the order (or company) of the other layers in its shape group."""
    params = _params()
    cfg = ShampooConfig()
    kron = _refreshed_state(params, _grads(params), cfg)["kron"]
    ups = {
        path: jax.random.normal(
            jax.random.PRNGKey(i),
            (
                e["ok"].shape[0],
                e["lroot"].shape[-1],
                e["rroot"].shape[-1],
            ),
        )
        for i, (path, e) in enumerate(kron.items())
    }
    fwd = sh.precondition(ups, kron)
    # reversed insertion order permutes every group's member stacking
    rev_paths = list(kron)[::-1]
    kron_r = {p: kron[p] for p in rev_paths}
    ups_r = {p: ups[p] for p in rev_paths}
    rev = sh.precondition(ups_r, kron_r)
    for path in fwd:
        np.testing.assert_array_equal(
            np.asarray(fwd[path]), np.asarray(rev[path])
        )
    # and each layer alone reproduces its grouped result bitwise
    for path in fwd:
        alone = sh.precondition({path: ups[path]}, {path: kron[path]})
        np.testing.assert_array_equal(
            np.asarray(fwd[path]), np.asarray(alone[path])
        )


def test_property_group_permutation_invariance():
    hyp = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis"
    )
    from hypothesis import given, settings, strategies as st

    cfg = ShampooConfig()

    @given(st.permutations(list(range(4))), st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def prop(perm, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 8)
        params = {f"w{i}": jax.random.normal(ks[i], (8, 12)) for i in range(4)}
        grads = {
            f"w{i}": jax.random.normal(ks[4 + i], (8, 12)) for i in range(4)
        }
        kron = _refreshed_state(params, grads, cfg)["kron"]
        ups = {p: g.reshape(1, 8, 12) for p, g in grads.items()}
        base = sh.precondition(ups, kron)
        names = [f"w{i}" for i in perm]
        permuted = sh.precondition(
            {n: ups[n] for n in names}, {n: kron[n] for n in names}
        )
        for p in base:
            np.testing.assert_array_equal(
                np.asarray(base[p]), np.asarray(permuted[p])
            )

    prop()


def test_inverse_root_methods_agree():
    # rank-deficient on purpose: the early-training shape (an EMA of a few
    # gradient outer products) that the lambda_max-relative ridge exists for
    g = jax.random.normal(jax.random.PRNGKey(3), (24, 16))
    s = g @ g.T
    re, oke = sh.inverse_quarter_root(s, method="eigh")
    rn, okn = sh.inverse_quarter_root(s, method="newton", iters=30)
    assert bool(oke) and bool(okn)
    scale = float(jnp.max(jnp.abs(re)))
    np.testing.assert_allclose(
        np.asarray(re), np.asarray(rn), atol=1e-4 * scale
    )
    # actually an inverse quarter root: root^4 (S + ridge I) ~ I
    ridge = sh._ridge_of(s, 1e-2)
    r4 = re @ re @ re @ re
    np.testing.assert_allclose(
        np.asarray(r4 @ (s + ridge * jnp.eye(24))), np.eye(24),
        atol=5e-3,
    )


# ---------------------------------------------------------------------------
# Refresh cadence, staleness, checkpoint round-trip
# ---------------------------------------------------------------------------


def test_refresh_cadence_and_stale_counter():
    params = _params()
    cfg = ShampooConfig(precond_every=3)
    st = sh.shampoo_init(params, cfg)
    step = jax.jit(lambda g, s: sh.shampoo_update(g, s, params, cfg))
    stales = []
    for i in range(7):
        _, st, m = step(_grads(params, seed=i), st)
        stales.append(int(m["precond_stale_steps"]))
    # refreshes at steps 1, 3, 6 -> stale resets there, counts up between
    assert stales == [0, 1, 0, 1, 2, 0, 1]
    assert all(bool(e["ok"].all()) for e in st["kron"].values())


def test_state_roundtrips_through_checkpoint(tmp_path):
    params = _params()
    cfg = ShampooConfig()
    st = _refreshed_state(params, _grads(params), cfg)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(7, st)
    target = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), st
    )
    back = mgr.restore(target)
    flat_a = jax.tree_util.tree_flatten_with_path(st)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(back)[0]
    assert [k for k, _ in flat_a] == [k for k, _ in flat_b]
    for (_, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Guard: chaos-injected refresh failure, numerics policy
# ---------------------------------------------------------------------------


def test_chaos_root_refresh_degrades_layer_not_step():
    params, grads = _params(), _grads(_params())
    cfg = ShampooConfig()
    st = sh.shampoo_init(params, cfg)
    with chaos.inject("root_refresh:times=1") as specs:
        newp, st1, m = sh.shampoo_update(grads, st, params, cfg)
    assert specs[0].fired == 1
    # the step completed; exactly one leaf lost its refresh for the interval
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(newp))
    down = [p for p, e in st1["kron"].items() if not bool(e["ok"].any())]
    up = [p for p, e in st1["kron"].items() if bool(e["ok"].all())]
    assert len(down) == 1 and up
    # degraded leaf: roots still identity (kept), stale kept counting
    e = st1["kron"][down[0]]
    np.testing.assert_array_equal(
        np.asarray(e["lroot"]), np.asarray(st["kron"][down[0]]["lroot"])
    )
    assert int(e["stale"].max()) == 1
    # and the event is in guard health
    assert guard.health_report()["events"]["root_refresh_degraded"] >= 1
    # the degraded layer's update IS the grafted-AdamW fallback: bitwise
    # equal to a plain AdamW step on the same grads (fresh state both ways)
    ap, _, _ = opt_update(grads, opt_init(params, OptConfig()), params,
                          OptConfig())
    by_path_sh = {
        sh._leaf_path(kp): l
        for kp, l in jax.tree_util.tree_flatten_with_path(newp)[0]
    }
    by_path_ad = {
        sh._leaf_path(kp): l
        for kp, l in jax.tree_util.tree_flatten_with_path(ap)[0]
    }
    np.testing.assert_array_equal(
        np.asarray(by_path_sh[down[0]]), np.asarray(by_path_ad[down[0]])
    )
    # while a healthy preconditioned layer diverged from plain AdamW
    assert not np.array_equal(
        np.asarray(by_path_sh[up[0]]), np.asarray(by_path_ad[up[0]])
    )


def test_numerics_policy_warn_and_raise():
    params = _params()
    grads = _grads(params)
    # poison one eligible leaf -> its statistics (and roots) go non-finite
    grads["head"] = grads["head"].at[0, 0].set(jnp.nan)
    cfg = ShampooConfig()
    st = sh.shampoo_init(params, cfg)
    with guard.numerics("warn"):
        with pytest.warns(guard.GuardWarning, match="inverse-root"):
            _, st1, _ = sh.shampoo_update(grads, st, params, cfg)
    assert guard.health_report()["events"]["root_refresh_degraded"] >= 1
    guard.reset_health()
    with guard.numerics("raise"):
        with pytest.raises(guard.NumericsError):
            sh.shampoo_update(grads, st, params, cfg)
    # off: silent, but the poisoned layer still degrades via its ok flag
    _, st2, m = sh.shampoo_update(grads, st, params, cfg)
    assert not bool(st2["kron"]["head"]["ok"].any())
    assert float(m["precond_ok_frac"]) < 1.0


# ---------------------------------------------------------------------------
# Telemetry: spans + zero-compiled-HLO pin on the optimizer path
# ---------------------------------------------------------------------------


def test_spans_and_histograms_when_active():
    params, grads = _params(), _grads(_params())
    cfg = ShampooConfig()
    st = sh.shampoo_init(params, cfg)
    telemetry.configure()
    sh.shampoo_update(grads, st, params, cfg)
    snap = telemetry.snapshot()
    assert "span.optim.root_refresh" in snap["histograms"]
    assert "span.optim.precondition" in snap["histograms"]


def test_telemetry_off_adds_zero_hlo_to_optimizer_step():
    params, grads = _params(), _grads(_params())
    cfg = ShampooConfig(precond_every=2)
    st = sh.shampoo_init(params, cfg)

    def compiled_text():
        return (
            jax.jit(lambda g, s: sh.shampoo_update(g, s, params, cfg))
            .lower(grads, st)
            .compile()
            .as_text()
        )

    off_before = compiled_text()
    assert "kronscope" not in off_before
    telemetry.configure()
    on = compiled_text()
    telemetry.reset()
    off_after = compiled_text()
    assert strip_source_locations(off_before) == strip_source_locations(off_after)
    assert "kronscope" not in off_after
    del on  # annotation side of the pin is covered in test_telemetry


# ---------------------------------------------------------------------------
# Dispatch, shardings, memory report
# ---------------------------------------------------------------------------


def test_opt_for_dispatch():
    assert sh.opt_for(OptConfig()) == (opt_init, opt_update)
    init_fn, update_fn = sh.opt_for(ShampooConfig())
    assert init_fn is sh.shampoo_init and update_fn is sh.shampoo_update


def test_opt_state_shardings_structure():
    from repro.train.steps import opt_state_shardings

    params = _params()
    cfg = ShampooConfig()
    st = sh.shampoo_init(params, cfg)
    PSH = object()
    p_shard = jax.tree.map(lambda _: PSH, params)
    REP = object()
    shard = opt_state_shardings(st, p_shard, REP)
    assert all(s is PSH for s in jax.tree.leaves(shard["m"]))
    assert all(s is PSH for s in jax.tree.leaves(shard["v"]))
    assert shard["step"] is REP
    assert all(s is REP for s in jax.tree.leaves(shard["kron"]))


def test_state_memory_report():
    params = _params()
    st = sh.shampoo_init(params, ShampooConfig(state_dtype="bfloat16"))
    rep = sh.state_memory_report(st)
    total = sum(
        int(l.size) * jnp.dtype(l.dtype).itemsize for l in jax.tree.leaves(st)
    )
    assert rep["total_bytes"] == total == sum(rep["by_dtype"].values())
    assert rep["by_dtype"]["bfloat16"] > 0  # m/v + statistics in bf16
    assert rep["by_dtype"]["float32"] > 0   # roots stay f32


def test_bf16_state_dtype_halves_mv():
    params = _params()
    st32 = sh.shampoo_init(params, ShampooConfig())
    st16 = sh.shampoo_init(params, ShampooConfig(state_dtype="bfloat16"))
    b32 = sh.state_memory_report({"m": st32["m"], "v": st32["v"]})
    b16 = sh.state_memory_report({"m": st16["m"], "v": st16["v"]})
    assert b16["total_bytes"] * 2 == b32["total_bytes"]


# ---------------------------------------------------------------------------
# End to end: the acceptance training run (slow)
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from repro.configs import get_config
    from repro.models.config import reduced

    return reduced(
        get_config("qwen3_4b"), n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, vocab=64,
        vocab_pad_multiple=32, dtype="float32",
    )


@pytest.mark.slow
def test_shampoo_reaches_adamw_loss_at_same_steps():
    """Fixed seed, reduced config, 80 steps: the Kron-preconditioned run
    must reach a loss <= AdamW's (the BENCH_optim acceptance bar)."""
    from repro.data import SyntheticLM
    from repro.train.steps import make_train_step, train_state_init

    cfg = _tiny_cfg()

    def run(ocfg, steps=80):
        data = SyntheticLM(vocab=cfg.vocab, seq_len=32, batch=8)
        state = train_state_init(cfg, ocfg, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, ocfg))
        for i in range(steps):
            toks, labels = data.global_batch(i)
            state, m = step(
                state,
                {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)},
            )
        return float(m["loss"])

    kw = dict(lr=3e-3, warmup_steps=5, decay_steps=80)
    adamw_loss = run(OptConfig(**kw))
    shampoo_loss = run(
        ShampooConfig(
            precond_every=10, stats_beta=0.95, matrix_eps=3e-2, **kw
        )
    )
    assert shampoo_loss <= adamw_loss, (shampoo_loss, adamw_loss)


@pytest.mark.slow
def test_shampoo_jit_train_step_refreshes_in_graph():
    """The refresh is a lax.cond inside ONE compiled step: no retraces
    across the cadence boundary (zero mid-training re-plans)."""
    from repro.data import SyntheticLM
    from repro.train.steps import make_train_step, train_state_init

    cfg = _tiny_cfg()
    ocfg = ShampooConfig(lr=1e-3, warmup_steps=2, decay_steps=20,
                         precond_every=3)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32, batch=4)
    state = train_state_init(cfg, ocfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, ocfg))
    for i in range(7):
        toks, labels = data.global_batch(i)
        state, m = step(
            state,
            {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)},
        )
    assert step._cache_size() == 1
    assert all(bool(e["ok"].all()) for e in state.opt["kron"].values())
