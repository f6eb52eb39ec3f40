"""The expert-layer training cell (``dsv2lite-train``, kind ``moe_lm``) at
tiny size on the CPU: the reference against the program, what decides
``correct`` (sound runs pass; the control and each fault fail), the work
counts, and the readers of the cell's per-layer metrics on a hand-made
trace and scope map."""
import dataclasses
import json
import math
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness, moe_control, trace
from bench.kinds import moe_lm
from bench.reference import moe_lm as ref
from bench.tests.tiny import make_root

CELL, CONFIG, TRAFFIC = "dsv2lite-train", "deepseek-v2-lite-kronffn", "train_b1_s4096"
SEED = 2**31 + 77
# Every width cut, every mechanism kept: MLA with YaRN, a dense layer 0,
# then routed top-2 of 4 experts and 2 shared, a Kron product each.
TINY_CONFIG = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
               "n_routed_experts": 4, "num_experts_per_tok": 2, "num_attention_heads": 4,
               "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
               "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
               "vocab_size": 256}
TINY_TRAFFIC = {"batch": 2, "seq": 32, "token_pool": 8}
# CPU at the tiny size, 12 seeds: sound runs read loss_gap up to 5.8e-4,
# grad_norm_gap 6.1e-2, median_grad_norm_gap 1.6e-2, change_norm_gap 0.110;
# the fp8 control at least 6.4e-4, 0.16, 2.3e-2, 0.135 (3 seeds); half a
# batch at least 5.2e-3, 0.29, 6.0e-2, 0.23; top-k weights renormalised at
# least 2.0e-4, 8.6e-2, 1.4e-2, 7.7e-2 (4 experts, top-2: the renormalised
# weights are only 1.3-1.6 times the scores here).
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 8e-2, "median_grad_norm_gap": 2e-2,
               "change_norm_gap": 0.125}


def tiny_root(dest):
    """``tiny.make_root`` with this cell's configuration, traffic and limits
    cut to the tiny size."""
    root = make_root(dest)
    bench = root / "bench"
    for path, over in ((bench / "configs" / f"{CONFIG}.json", TINY_CONFIG),
                       (bench / "traffic" / f"{TRAFFIC}.json", TINY_TRAFFIC)):
        path.write_text(json.dumps({**json.loads(path.read_text()), **over}))
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMITS))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny_moe"))


def _config(**over):
    cfg = json.loads((harness.BENCH / "configs" / f"{CONFIG}.json").read_text())
    return {**cfg, **TINY_CONFIG, **over}


def _driver(cfg):
    import jax

    traffic = json.loads((harness.BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
    cell = dataclasses.make_dataclass("C", ["config", "config_name", "traffic", "chips"])(
        cfg, "tiny", traffic, 1)
    return moe_lm.Driver(cell, 0, jax.devices()[:1], log=lambda m: None)


@pytest.mark.parametrize("capacity_factor", [1.25, 0.5])
def test_reference_matches_the_program_in_float32(capacity_factor):
    """Same weights, same rows: the reference's loss and every gradient
    against the program's ``loss_fn`` in float32.  At 0.5 each expert's
    bucket is full and tokens are dropped, by the same rule on both sides.
    Tolerances: float32 sums in different orders (the Kron chain against
    the dense kron matrix, blocked against whole attention), 1e-5 of the
    loss and 2e-3 of a gradient element with a 2e-6 floor, as for the dense
    cell's reference."""
    import jax
    import jax.numpy as jnp
    from repro.train import loss_fn

    from bench.kinds.lm import make_params

    cfg = _config(capacity_factor=capacity_factor)
    driver = _driver(cfg)
    program = dataclasses.replace(driver.cfg, dtype="float32")
    params = make_params(driver._param_shapes(), jax.random.PRNGKey(3), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, cfg["vocab_size"])
    tokens, labels = toks[:, :-1], toks[:, 1:]
    with jax.default_matmul_precision("highest"):
        (p_loss, parts), p_grad = jax.value_and_grad(
            lambda p: loss_fn(program, p, tokens, labels), has_aux=True)(params)
    r_loss, r_grad = jax.value_and_grad(lambda p: ref.loss(cfg, p, tokens, labels))(params)
    assert float(parts["aux"]) > 0
    assert float(p_loss) == pytest.approx(float(r_loss), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p_grad),
                            jax.tree.leaves(r_grad)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_reference_yarn_and_scale_at_the_published_numbers():
    cfg = json.loads((harness.BENCH / "configs" / f"{CONFIG}.json").read_text())
    assert ref.yarn_range(cfg) == (10, 23)
    # (0.1 * 0.707 * ln 40 + 1)^2 / sqrt(128 + 64)
    assert ref.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    assert ref.capacity(cfg, 4096) == 488


def test_reference_drop_rule_keeps_the_first_tokens_of_each_expert():
    """Three tokens choose expert 0, capacity 8 of 16 tokens: an expert's
    bucket takes the first 8 that chose it, in token order."""
    import jax.numpy as jnp

    cfg = {**_config(), "n_routed_experts": 2, "num_experts_per_tok": 1,
           "capacity_factor": 0.5}
    s, d = 16, 64
    assert ref.capacity(cfg, s) == 8
    top_i = jnp.asarray([[0] * 12 + [1] * 4])[..., None]  # 12 tokens choose expert 0
    top_p = jnp.ones((1, s, 1))
    x = jnp.ones((1, s, d))
    eye = tuple(jnp.stack([jnp.eye(n)] * 2) for n in (8, 8))
    p = {"ew1": {"factors": eye}, "ew3": {"factors": eye}, "ew2": {"factors": eye}}
    y = ref.routed_experts(cfg, p, x, top_i, top_p, "highest")
    kept = np.asarray(jnp.abs(y).sum(-1)[0] > 0)
    assert kept.tolist() == [True] * 8 + [False] * 4 + [True] * 4


def _fails(checks):
    return [k for k, c in checks.items() if not c["value"] <= c["limit"]]


def test_sound_run_passes_and_controls_fail(tiny):
    log = lambda m: None  # noqa: E731
    program, ctrl = moe_control.readings(CELL, [SEED], [SEED], root=tiny,
                                         require_tpu=False, log=log)
    assert not _fails(program["checks"]), program
    assert _fails(ctrl["checks"]), ctrl


@pytest.mark.parametrize("fault", moe_control.FAULTS)
def test_faults_fail(tiny, fault):
    (r,) = moe_control.readings(CELL, [], [SEED], fault, root=tiny, require_tpu=False,
                                log=lambda m: None)
    assert r["what"] == fault
    assert _fails(r["checks"]), r


def test_half_batch_of_one_row_is_half_its_positions(tmp_path):
    """The cell's batch is one row: half of it is the row's first half."""
    root = tiny_root(tmp_path)
    path = root / "bench" / "traffic" / f"{TRAFFIC}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "batch": 1}))
    (r,) = moe_control.readings(CELL, [], [SEED], "half_batch", root=root,
                                require_tpu=False, log=lambda m: None)
    assert len(_fails(r["checks"])) == 4, r


def _broken_step(kind):
    import repro.train

    make = repro.train.make_train_step

    def factory(cfg, opt_cfg, **kw):
        step = make(cfg, opt_cfg, **kw)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def renorm_topk(state, batch):
            moe = dataclasses.replace(cfg.moe, norm_topk_prob=True)
            return make(dataclasses.replace(cfg, moe=moe), opt_cfg, **kw)(state, batch)

        return {"unchanged": unchanged, "renorm_topk": renorm_topk}[kind]

    return factory


@pytest.mark.parametrize("fault", ["unchanged", "renorm_topk"])
def test_broken_train_step_is_not_correct(tiny, fault, monkeypatch):
    import repro.train

    monkeypatch.setattr(repro.train, "make_train_step", _broken_step(fault))
    out = harness.run(CELL, SEED, 0.2, False, t0=time.perf_counter(), root=tiny,
                      require_tpu=False, log=lambda m: None)
    assert not out["correct"], out["checks"]


def test_a_run_reports_the_cells_metrics(tiny):
    out = harness.run(CELL, SEED, 0.2, False, t0=time.perf_counter(), root=tiny,
                      require_tpu=False, log=lambda m: None)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_queue_is_sized_from_the_device_peak_alone(tiny, monkeypatch):
    """The warm-up steps ran the program, so the device's peak holds its
    temporaries: the queue does not count them a second time."""
    from bench.kinds import lm

    seen = []
    monkeypatch.setattr(lm, "queue_depth", lambda *a, **kw: seen.append(kw) or 3)
    cell = harness.resolve(CELL, tiny)
    import jax

    d = moe_lm.Driver(cell, SEED, jax.devices()[:1], log=lambda m: None)
    d.setup(seconds=0.1)
    assert d.ahead == 3 and seen == [{}]


def test_moe_control_takes_several_faults_in_one_process(monkeypatch, capsys):
    """``--fault`` repeats: each fault in turn on the control seeds, the
    sound seeds once."""
    calls = []

    def readings(workload, seeds, control_seeds, fault, **kw):
        calls.append((list(seeds), list(control_seeds), fault))
        yield {"seed": 1, "what": fault or "program", "checks": {}}

    monkeypatch.setattr(moe_control, "readings", readings)
    monkeypatch.setattr("sys.argv", ["moe_control.py", "--workload", CELL, "--seeds", "4",
                                     "--control-seeds", "5", "--fault", "unchanged",
                                     "--fault", "renorm_topk"])
    assert moe_control.main() == 0
    assert calls == [([4], [5], "unchanged"), ([], [5], "renorm_topk")]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["what"] for r in lines] == ["unchanged", "renorm_topk"]


def test_moe_control_refuses_another_kind(tiny):
    with pytest.raises(harness.BenchError, match="not 'moe_lm'"):
        next(moe_control.readings("qwen3-4b-train", [1], root=tiny, require_tpu=False))


# -- the work counts -------------------------------------------------------


def test_expert_kron_work_counts_the_routed_slots():
    from bench.work import kron_fwdbwd_flops

    cfg = json.loads((harness.BENCH / "configs" / f"{CONFIG}.json").read_text())
    shapes = moe_lm.expert_kron_shapes(cfg)
    assert shapes == [((64, 32), (44, 32)), ((64, 32), (44, 32)), ((44, 32), (64, 32))]
    work = moe_lm.expert_kron_work(cfg, 4096, 2)
    layers = cfg["num_hidden_layers"] - 1
    slots = 4096 * 6
    assert work[0]["flops"] == layers * kron_fwdbwd_flops(slots, (64, 32), (44, 32))
    # x, y, dY, dX of the slots and F, dF of each of the 64 experts, in bf16
    x, y, f = slots * 2048, slots * 1408, 64 * 44 + 32 * 32
    assert work[0]["bytes"] == layers * 2 * (2 * x + 2 * y + 64 * 2 * f)


def test_flops_per_token_counts_active_parameters():
    """At least 6 FLOPs per active matmul parameter, and the Kron experts
    add far less than dense experts of the same widths would."""
    cfg = json.loads((harness.BENCH / "configs" / f"{CONFIG}.json").read_text())
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    attn = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    got = moe_lm.flops_per_token(cfg, 4096)
    floor = 6 * (layers * attn + d * cfg["vocab_size"]) + 6 * 4096 * 16 * 320 * layers
    dense_experts = 6 * 3 * d * 1408 * 8 * (layers - 1)  # 6 routed + 2 shared
    assert floor < got < floor + dense_experts / 10


# -- the readers, on a hand-made trace and scope map -----------------------

US = 1000.0
CHAINS = {"kron_chain_fwd.1": "model.ffn/model.moe.experts/op/program/stage",
          "fusion.2": "model.ffn/model.moe.experts",
          "kron_stage_grad.3": "model.ffn/model.moe.experts/op/op_bwd/stage_grad",
          "gather.4": "model.ffn/model.moe.dispatch", "sort.5": "model.ffn/model.moe.router",
          "gather.6": "model.ffn/model.moe.combine",
          "kron_chain_fwd.7": "model.ffn/model.moe.shared/op/program/stage",
          "fusion.8": "model.attn"}
OPS = [("kron_chain_fwd.1", 0, 10), ("fusion.2", 10, 4), ("kron_stage_grad.3", 14, 20),
       ("gather.4", 40, 3), ("sort.5", 43, 2), ("gather.6", 45, 5),
       ("kron_chain_fwd.7", 50, 8), ("fusion.8", 60, 30)]


def _summary(ops=OPS):
    ev = lambda n, s, d: NS(name=n, start_ns=s * US, duration_ns=d * US)  # noqa: E731
    planes = [NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev(trace.WINDOW_SPAN, 0, 100)])]),
        NS(name="/device:TPU:0", lines=[NS(name=trace.OPS_LINE, events=[
            ev(f"%{n} = f32[8] op(%x)", s, d) for n, s, d in ops])])]
    return trace.reduce(NS(planes=planes), ("kron_chain_fwd", "kron_stage_grad"))


def _run(work, calls=2):
    return NS(work=work, trace=_summary(), calls=calls, chips=1,
              peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def _read(metric, run):
    return harness.load_reader(harness.BENCH, metric)(run)


def test_experts_and_route_ms_read_their_scopes():
    run = _run({"scopes": CHAINS})
    assert _read("moe.experts_ms", run) == pytest.approx(34e-6 / 2 * 1e3)
    assert _read("moe.route_ms", run) == pytest.approx(10e-6 / 2 * 1e3)


def test_kron_roofline_is_least_time_over_the_experts_device_time():
    # per step: 17 us in the experts' scope; least time 8.5 us (compute
    # bound, 8.5e6 FLOPs at 1e12/s) + 3 us (memory bound, 3e5 bytes at 1e11/s)
    work = {"scopes": CHAINS, "expert_kron": [{"flops": 8.5e6, "bytes": 1e3},
                                              {"flops": 1e3, "bytes": 3e5}]}
    assert _read("moe.kron_roofline", _run(work)) == pytest.approx(100 * 11.5 / 17)


def test_kron_roofline_is_at_most_100_when_the_kernels_run_at_the_floor():
    """Work whose least time is exactly the experts' device time reads 100%."""
    work = {"scopes": CHAINS, "expert_kron": [{"flops": 17e6, "bytes": 0}]}
    assert _read("moe.kron_roofline", _run(work)) == pytest.approx(100.0)
    work = {"scopes": CHAINS, "expert_kron": [{"flops": 8e6, "bytes": 9e5}]}
    assert _read("moe.kron_roofline", _run(work)) <= 100.0


@pytest.mark.parametrize("metric", ["moe.experts_ms", "moe.route_ms", "moe.kron_roofline"])
def test_readers_read_nothing_without_the_scopes(metric):
    """A program without the ``model.moe.*`` scopes, or a kind that hands
    over no scope map, reads nothing and raises nothing."""
    bare = {k: v for k, v in CHAINS.items() if "moe" not in v}
    for work in ({}, {"scopes": {}}, {"scopes": bare, "expert_kron": [{"flops": 1, "bytes": 1}]}):
        assert _read(metric, _run(work)) is None


def test_readers_in_a_traced_run_of_the_cell(tiny, monkeypatch):
    """A traced run at tiny size: the kind hands over the program's own
    scope map, in which the readers find their scopes (the CPU trace, which
    has no TPU planes, is stood in for by the hand-made one)."""
    monkeypatch.setattr(trace, "reduce_dir", lambda d, kernel_names=(): _summary())
    out = harness.run(CELL, SEED, 0.2, True, t0=time.perf_counter(), root=tiny,
                      require_tpu=False, log=lambda m: None)
    got = out["metrics"]
    for name in ("moe.experts_ms", "moe.route_ms", "step.mfu_pct", "device.idle_pct.train"):
        assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0, name


def test_reference_update_keeps_parameters_on_the_stored_grid():
    """The reference holds its parameters in float32 between steps; after
    each update they are exactly representable in the stored dtype, as a
    bf16 parameter is (``reduce_precision``, which XLA keeps where it may
    drop an f32 -> bf16 -> f32 round trip)."""
    import jax
    import jax.numpy as jnp

    p = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 0.15}
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 64)) * 1e-3}
    m, v = ({"w": jnp.zeros((64, 64))} for _ in range(2))
    opt = json.loads((harness.BENCH / "traffic" / f"{TRAFFIC}.json").read_text())["optimizer"]
    start = p["w"]
    new, _, _, _ = ref._update(dict(p, w=start + 0), m, v, g, jnp.int32(0),
                               opt_items=tuple(sorted(opt.items())), store="bfloat16")
    w = new["w"]
    assert w.dtype == jnp.float32
    np.testing.assert_array_equal(w, w.astype(jnp.bfloat16).astype(jnp.float32))
    assert float(jnp.abs(w - start).max()) > 0
