"""Autotuner (C5): analytic model sanity + measured ranking + plan cache."""
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.autotune import (
    TileConfig,
    candidate_tiles,
    load_plan_cache,
    make_plan,
    measure_best,
    plan_cache_key,
    plan_from_json,
    plan_to_json,
    predict_seconds,
    tune_sliced,
    vmem_elems,
)
from repro.core.kron import KronProblem
from repro.kernels.kron_fused import fused_growth


def test_candidates_respect_vmem():
    cands = candidate_tiles(m=1024, s=4096, p=64, q=64)
    assert cands
    for c in cands:
        assert vmem_elems(c, 64) * 4 <= 16 * 1024 * 1024 * 3 // 4


def test_predict_prefers_deeper_contraction():
    """The model must know the MXU: P=128 beats P=8 at equal FLOPs/byte."""
    cfg = TileConfig(8, 64, 8)
    t_small = predict_seconds(1024, 512, 8, 8, cfg)
    t_big = predict_seconds(1024, 32, 128, 128, TileConfig(8, 32, 128))
    # big-P case has 16x the FLOPs but >=16x the MXU utilization
    assert t_big < t_small * 32


def test_tune_sliced_returns_dividing_tiles():
    for (m, s, p, q) in [(1024, 512, 8, 8), (16, 64, 64, 64), (7, 9, 3, 5)]:
        c = tune_sliced(m, s, p, q)
        assert m % c.t_m == 0 and s % c.t_s == 0 and q % c.t_q == 0


def test_plan_fusion_groups_small_p():
    # P=4, N=6: fusion should chain multiple factors per stage
    plan = make_plan(KronProblem.uniform(64, 4, 4, 6), enable_prekron=False)
    assert any(len(st.factor_ids) > 1 for st in plan.stages)


def test_plan_no_fusion_when_disabled():
    plan = make_plan(
        KronProblem.uniform(64, 4, 4, 6),
        enable_prekron=False,
        enable_fusion=False,
    )
    assert all(len(st.factor_ids) == 1 for st in plan.stages)


def test_plan_stages_respect_vmem_budget():
    """Every fused stage's (t_m, T_K, growth) must fit the kernel's VMEM
    budget — including expanding chains where Q-tiling provides the relief."""
    budget = 2 * 1024 * 1024
    for prob in [
        KronProblem.uniform(64, 4, 4, 6),
        KronProblem.uniform(256, 16, 16, 4),
        KronProblem(64, (2, 2, 2, 2, 2), (8, 8, 8, 8, 8)),    # growth, untiled
        KronProblem(64, (2, 2, 2, 2, 2), (32, 32, 32, 32, 32)),  # Q-tiled
        KronProblem(32, (4, 2, 8), (8, 4, 2)),
    ]:
        plan = make_plan(prob, enable_prekron=False, vmem_budget_elems=budget)
        ps = list(reversed(prob.ps))
        qs = list(reversed(prob.qs))
        for st in plan.stages:
            if len(st.factor_ids) <= 1:
                continue
            sps = [ps[i] for i in st.factor_ids]
            sqs = [qs[i] for i in st.factor_ids]
            t_k = st.tiles.t_s * math.prod(sps)
            growth = fused_growth(sps, sqs, st.t_qs)
            assert st.tiles.t_m * t_k * growth <= budget, (
                prob, st, t_k, growth
            )


def test_plan_q_tiling_extends_fusion_on_expanding_chains():
    """Expanding chains (Q >> P) fuse further than the untiled budget allows
    because the plan Q-tiles the growing factors."""
    prob = KronProblem(64, (2, 2, 2, 2, 2), (32, 32, 32, 32, 32))
    plan = make_plan(prob, enable_prekron=False)
    assert any(
        len(st.factor_ids) > 1 and st.t_qs is not None for st in plan.stages
    ), plan.describe()


def test_plan_has_mirrored_bwd_stages():
    prob = KronProblem(16, (4, 2, 3), (3, 2, 4))
    plan = make_plan(prob, enable_prekron=False)
    assert plan.bwd_stages is not None
    fwd_ids = [st.factor_ids for st in plan.stages]
    bwd_ids = [st.factor_ids for st in plan.bwd_stages]
    assert bwd_ids == list(reversed(fwd_ids))


def test_plan_json_roundtrip():
    prob = KronProblem(64, (2, 2, 2, 2, 2), (8, 8, 8, 8, 8))
    plan = make_plan(prob, enable_prekron=False)
    assert plan_from_json(plan_to_json(plan)) == plan


def test_measured_plan_cache_hit_skips_measurement(tmp_path):
    """tune="measure" persists the winner; the second call must not measure
    (we poison measure_best to prove the cache path is taken)."""
    import repro.core.autotune as at

    cache = str(tmp_path / "plans.json")
    prob = KronProblem(8, (4, 4), (4, 4))
    plan1 = make_plan(prob, tune="measure", backend="xla", cache_path=cache)
    assert os.path.exists(cache)
    key = plan_cache_key(prob, 4, "xla")
    entries = load_plan_cache(cache)
    assert key in entries and entries[key]["seconds"] > 0

    orig = at.measure_best
    at.measure_best = lambda *a, **k: (_ for _ in ()).throw(
        AssertionError("measure_best called on cache hit")
    )
    try:
        plan2 = make_plan(prob, tune="measure", backend="xla", cache_path=cache)
    finally:
        at.measure_best = orig
    assert plan2 == plan1


@pytest.mark.parametrize(
    "garbage",
    [
        "not json at all {{{",
        '{"version": 1, "entries"',          # truncated mid-write
        '{"version": 99, "entries": {}}',    # wrong schema version
        '[1, 2, 3]',                         # valid JSON, wrong shape
        '{"version": 1, "entries": [1]}',    # entries not a dict
        '{"version": 1, "entries": {"k": {"seconds": 1}}}',  # entry sans plan
        "",                                  # empty file
    ],
)
def test_plan_cache_recovers_from_corrupt_file(tmp_path, garbage):
    """A corrupt/truncated cache (e.g. a concurrent writer died) degrades to
    an empty cache on load, and the next measured plan rewrites it whole."""
    cache = tmp_path / "plans.json"
    cache.write_text(garbage)
    assert load_plan_cache(str(cache)) == {}
    prob = KronProblem(8, (4, 4), (4, 4))
    plan = make_plan(prob, tune="measure", backend="xla", cache_path=str(cache))
    assert plan.stages
    entries = load_plan_cache(str(cache))
    key = plan_cache_key(prob, 4, "xla")
    assert key in entries  # cache healthy again


def test_plan_cache_save_merges_concurrent_entries(tmp_path):
    """Two writers that loaded the same snapshot don't clobber each other:
    save merges the on-disk entries written in between."""
    from repro.core.autotune import save_plan_cache

    cache = str(tmp_path / "plans.json")
    save_plan_cache(cache, {"a": {"plan": {"stages": []}, "seconds": 1}})
    # second writer, unaware of 'a', saves only 'b'
    save_plan_cache(cache, {"b": {"plan": {"stages": []}, "seconds": 2}})
    entries = load_plan_cache(cache)
    assert set(entries) == {"a", "b"}


def test_measured_plan_records_candidate_set(tmp_path):
    """The unified measured path (single AND batched through one
    _measured_plan) records the candidate set it ranked in the cache entry —
    with the batched sweep widened over t_b divisors."""
    from repro.core.autotune import make_batched_plan

    cache = str(tmp_path / "plans.json")
    prob = KronProblem(8, (4, 4), (4, 4))
    make_plan(prob, tune="measure", backend="xla", cache_path=cache)
    make_batched_plan(
        prob, 8, shared_factors=False, tune="measure", backend="xla",
        cache_path=cache,
    )
    entries = load_plan_cache(cache)
    single_key = plan_cache_key(prob, 4, "xla")
    batched_key = plan_cache_key(
        prob, 4, "xla", enable_prekron=False, batch=8, shared_factors=False
    )
    assert set(entries) == {single_key, batched_key}
    for key in entries:
        assert len(entries[key]["candidates"]) >= 2, entries[key]
    # widened t_b sweep: batched candidates cover multiple batch tiles
    tbs = {
        c.split("t_b=")[1].split("]")[0]
        for c in entries[batched_key]["candidates"]
        if "t_b=" in c
    }
    assert len(tbs) > 1, entries[batched_key]["candidates"]


def test_measure_best_ranks_by_wallclock():
    """measure_best picks the candidate whose closure is actually fastest."""
    x = jnp.zeros((256, 256))

    def fn_of_cfg(cfg):
        if cfg.t_m == 1:  # deliberately slow candidate
            return lambda: sum(x @ x for _ in range(8)) / 8
        return lambda: x @ x

    best, dt = measure_best(
        fn_of_cfg, [TileConfig(1, 1, 1), TileConfig(8, 8, 8)], warmup=1, iters=2
    )
    assert best.t_m == 8 and dt > 0


def test_measure_best_records_dropped_candidates():
    from repro.runtime import guard

    guard.reset_health()

    def fn_of(cfg):
        if cfg == "bad":
            raise ValueError("no such tiling")
        return lambda: jnp.ones(4)

    best, _ = measure_best(fn_of, ["bad", "good"], warmup=0, iters=1)
    assert best == "good"
    events = guard.health_report()["events"]
    assert events["measure_dropped"] == 1
    assert events["measure_dropped:ValueError"] == 1
    guard.reset_health()


def test_measure_best_propagates_bugs():
    def fn_of(cfg):
        raise TypeError("a bug, not a tiling the chip refuses")

    with pytest.raises(TypeError):
        measure_best(fn_of, ["a"], warmup=0, iters=1)


@pytest.mark.parametrize("m,s", [(1526, 256), (16, 4096), (20, 1)])
def test_candidate_tiles_are_legal_tpu_blocks(m, s):
    for c in candidate_tiles(m, s, 16, 16):
        assert c.t_m == m or c.t_m % 8 == 0
        assert c.t_s == s or c.t_s % 128 == 0
