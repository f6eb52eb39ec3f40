"""KronScope telemetry spine: spans, metrics, exports, profiling, and the
zero-overhead-off pin (docs/observability.md).

The structural contract mirrors the guard layer's (EXPERIMENTS.md
§Robustness): telemetry OFF must cost one truthiness check per site and add
NOTHING to compiled HLO — pinned here by comparing compiled text with
telemetry off, on, and off-again.  Telemetry ON must capture the whole
spine: spans nest and export as valid Chrome-trace JSON, guard/chaos
degradations land in the JSONL sink as events, and ``KronOp.profile``
reconciles measured stage times against the planner's analytic cost model.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.runtime import chaos, guard, telemetry
from repro.runtime.events import EventSink, get_logger
from repro.runtime.fault import StragglerMonitor
from repro.runtime.hlo_analysis import strip_source_locations


@pytest.fixture(autouse=True)
def _fresh_state():
    guard.reset_health()
    telemetry.reset()
    yield
    guard.reset_health()
    telemetry.reset()


def _problem(ps, qs, m=16, seed=0):
    rng = np.random.RandomState(seed)
    k = int(np.prod(ps))
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    fs = tuple(
        jnp.asarray(rng.randn(p, q), jnp.float32) for p, q in zip(ps, qs)
    )
    return x, fs


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


# ---------------------------------------------------------------------------
# Spans + exports
# ---------------------------------------------------------------------------


def test_span_nesting_and_exports(tmp_path):
    jl = tmp_path / "t.jsonl"
    tr = tmp_path / "t.trace.json"
    telemetry.configure(jsonl=str(jl), trace=str(tr))
    with telemetry.span("outer", tag="a"):
        with telemetry.span("inner"):
            pass
    snap = telemetry.shutdown()
    assert snap["spans"] == 2
    assert not telemetry.active()

    # JSONL: one valid object per line; inner completed first, nested deeper
    recs = _read_jsonl(jl)
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert spans["inner"]["depth"] == 1 and spans["outer"]["depth"] == 0
    assert spans["outer"]["dur"] >= spans["inner"]["dur"]
    assert spans["outer"]["attrs"] == {"tag": "a"}

    # Chrome trace: complete ("X") events with microsecond ts/dur
    trace = json.load(open(tr))
    events = trace["traceEvents"]
    assert {e["name"] for e in events} == {"outer", "inner"}
    for e in events:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)


def test_span_off_is_shared_noop():
    s1 = telemetry.span("anything", x=1)
    s2 = telemetry.span("else")
    assert s1 is s2  # one shared object: no per-site allocation when off
    with s1:
        pass


def test_op_call_records_program_and_stage_spans(tmp_path):
    op = engine.KronOp((4, 4), (4, 4))
    x, fs = _problem((4, 4), (4, 4))
    telemetry.configure(jsonl=str(tmp_path / "op.jsonl"))
    op(x, fs)
    snap = telemetry.shutdown()
    hists = snap["histograms"]
    assert "span.program" in hists
    assert "span.stage" in hists


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles():
    telemetry.configure()
    for v in range(1, 101):
        telemetry.observe("lat", float(v))
    p = telemetry.percentiles("lat")
    assert p["count"] == 100 and p["min"] == 1.0 and p["max"] == 100.0
    assert p["p50"] == 50.0 and p["p95"] == 95.0 and p["p99"] == 99.0
    assert abs(p["mean"] - 50.5) < 1e-9


def test_counters_gauges_and_snapshot():
    telemetry.configure()
    telemetry.counter_inc("c", 2)
    telemetry.counter_inc("c")
    telemetry.gauge_set("g", 3.5)
    telemetry.event("ping", detail="x")
    snap = telemetry.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["counters"]["event.ping"] == 1
    assert snap["gauges"]["g"] == 3.5
    assert snap["events"] == 1


def test_metrics_noop_when_off():
    telemetry.counter_inc("c")
    telemetry.gauge_set("g", 1.0)
    telemetry.observe("h", 1.0)
    telemetry.event("e")
    assert telemetry.percentiles("h") is None
    assert telemetry.snapshot() == {}
    assert telemetry.summary_line() == "kronscope[off]"


# ---------------------------------------------------------------------------
# Cost-model drift + KronOp.profile
# ---------------------------------------------------------------------------


def test_stage_drift_flags_outlier():
    # Stage 0 matches the whole-program calibration ratio exactly after
    # normalisation?  No: overall ratio is 11/2 = 5.5x, so stage 0 sits at
    # 1/5.5 (too fast vs its predicted share -> flagged) and stage 1 at
    # 10/5.5 = 1.8x (inside the 2x band -> clean).
    assert engine._stage_drift([1.0, 10.0], [1.0, 1.0], 2.0) == [True, False]
    # A uniform slowdown is calibration, not drift: nothing flags.
    assert engine._stage_drift([5.0, 5.0], [1.0, 1.0], 2.0) == [False, False]
    assert engine._stage_drift([], [], 2.0) == []


def test_profile_reconciles_with_cost_model():
    m, ps, qs = 32, (4, 4, 4), (4, 4, 4)
    op = engine.KronOp(ps, qs)
    x, fs = _problem(ps, qs, m=m)
    report = op.profile(x, fs, warmup=1, iters=2)
    assert len(report["stages"]) >= 1
    # stage flop accounting must agree exactly with the analytic model
    assert sum(s["flops"] for s in report["stages"]) == op.cost(m).flops
    assert report["cost_flops"] == op.cost(m).flops
    assert report["measured_s"] > 0 and report["predicted_s"] > 0
    shares = [s["share_measured"] for s in report["stages"]]
    assert abs(sum(shares) - 1.0) < 1e-9
    for s in report["stages"]:
        assert s["measured_s"] > 0 and s["predicted_s"] > 0
        assert isinstance(s["drift_flagged"], bool)
    assert report["signature"]["m"] == m
    assert report["drift_threshold"] == telemetry.DRIFT_THRESHOLD


def test_profile_stamps_registry_when_active(tmp_path):
    op = engine.KronOp((4, 4), (4, 4))
    x, fs = _problem((4, 4), (4, 4))
    telemetry.configure(jsonl=str(tmp_path / "p.jsonl"))
    op.profile(x, fs, warmup=0, iters=1)
    snap = telemetry.snapshot()
    assert snap["last_profile"] is not None
    assert snap["last_profile"]["stages"] == 1
    telemetry.shutdown()
    recs = _read_jsonl(tmp_path / "p.jsonl")
    assert any(
        r["kind"] == "event" and r["name"] == "profile" for r in recs
    )


def test_profile_unfused_raises():
    op = engine.KronOp((4, 4), (4, 4), plan=None)
    x, fs = _problem((4, 4), (4, 4))
    with pytest.raises(guard.PlanError, match="profile"):
        op.profile(x, fs)


# ---------------------------------------------------------------------------
# Guard/chaos integration: degradations land in the sink
# ---------------------------------------------------------------------------


def test_chaos_pallas_fault_emits_rung_fallback_event(tmp_path):
    # Explicit backend="pallas" keeps the pallas_lowering site reachable in
    # BOTH chaos-matrix legs: FASTKRON_FORCE_BACKEND only overrides "auto".
    op = engine.KronOp((4, 4), (4, 4), backend="pallas")
    x, fs = _problem((4, 4), (4, 4))
    ref = op(x, fs)
    guard.reset_health()
    jl = tmp_path / "chaos.jsonl"
    telemetry.configure(jsonl=str(jl))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", guard.GuardWarning)
        with chaos.inject("pallas_lowering:times=1"):
            y = op(x, fs)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(y))
    telemetry.shutdown()
    recs = _read_jsonl(jl)
    events = [r for r in recs if r["kind"] == "event"]
    names = [r["name"] for r in events]
    assert "chaos_injected" in names
    [fb] = [r for r in events if r["name"] == "rung_fallback"]
    assert fb["error"] == "LoweringError"
    assert fb["rung"] == 0
    [warned] = [r for r in events if r["name"] == "guard_warning"]
    assert "degrading" in warned["message"]


def test_health_report_merges_telemetry():
    assert "telemetry" not in guard.health_report()
    telemetry.configure()
    telemetry.counter_inc("plan_cache.hit", 4)
    report = guard.health_report()
    assert report["telemetry"]["counters"]["plan_cache.hit"] == 4


def test_describe_gains_summary_only_when_active():
    op = engine.KronOp((4, 4), (4, 4))
    assert "kronscope" not in op.describe()
    telemetry.configure()
    assert "kronscope[" in op.describe()
    telemetry.reset()
    assert "kronscope" not in op.describe()


def test_straggler_flag_becomes_event():
    telemetry.configure()
    mon = StragglerMonitor(action="callback", callback=lambda s, dt: None)
    for i in range(10):
        mon.observe(i, 1.0)
    mon.observe(10, 100.0)
    assert mon.flagged_steps
    assert telemetry.snapshot()["counters"]["event.straggler"] == 1


# ---------------------------------------------------------------------------
# Zero-overhead-off pin (the guard-style contract)
# ---------------------------------------------------------------------------


def test_telemetry_off_adds_zero_hlo():
    op = engine.KronOp((4, 4), (4, 4))
    x, fs = _problem((4, 4), (4, 4))

    def compiled_text():
        # fresh jit wrapper each call: no executable-cache aliasing between
        # the off/on/off lowering runs
        return (
            jax.jit(lambda x, fs: op(x, fs))
            .lower(x, fs)
            .compile()
            .as_text()
        )

    off_before = compiled_text()
    assert "kronscope" not in off_before

    telemetry.configure()
    on = compiled_text()
    assert "kronscope" in on  # named_scope reaches compiled metadata
    # stripping source locations keeps what telemetry adds
    assert "kronscope" in strip_source_locations(on)
    assert strip_source_locations(on) != strip_source_locations(off_before)

    telemetry.reset()
    off_after = compiled_text()
    # identical compiled HLO up to source line/column numbers (the two
    # compiles are called from different lines): enabling and disabling
    # telemetry leaves an untelemetered process where it started
    assert strip_source_locations(off_after) == strip_source_locations(off_before)


def test_annotate_false_keeps_hlo_clean():
    op = engine.KronOp((4, 4), (4, 4))
    x, fs = _problem((4, 4), (4, 4))
    telemetry.configure(annotate=False)
    txt = (
        jax.jit(lambda x, fs: op(x, fs)).lower(x, fs).compile().as_text()
    )
    assert "kronscope" not in txt
    assert telemetry.snapshot()["spans"] >= 1  # host timing still on


# ---------------------------------------------------------------------------
# Event sink + logger + bench provenance
# ---------------------------------------------------------------------------


def test_event_sink_appends_valid_lines(tmp_path):
    path = tmp_path / "sink.jsonl"
    sink = EventSink(str(path))
    sink.emit({"a": 1})
    sink.emit({"b": [1, 2]})
    sink.close()
    assert _read_jsonl(path) == [{"a": 1}, {"b": [1, 2]}]
    assert sink.emitted == 2


def test_get_logger_prints_bare_message(capsys):
    get_logger("repro.fault").warning("[straggler-monitor] hello")
    assert capsys.readouterr().out == "[straggler-monitor] hello\n"


def test_bench_meta_and_old_schema_reader(tmp_path):
    from benchmarks.util import bench_meta, load_bench

    meta = bench_meta()
    for key in ("jax", "jaxlib", "device_kind", "platform", "date"):
        assert meta[key]
    assert "git_sha" in meta

    old = tmp_path / "BENCH_old.json"
    old.write_text(json.dumps({"speedup": 2.0}))
    rec = load_bench(str(old))
    assert rec["speedup"] == 2.0 and rec["meta"] == {}

    new = tmp_path / "BENCH_new.json"
    new.write_text(json.dumps({"speedup": 2.0, "meta": meta}))
    assert load_bench(str(new))["meta"]["jax"] == meta["jax"]
