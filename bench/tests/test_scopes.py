"""Device time by the program's KronScope scopes (``bench/scopes.py``): the
join of a compiled program's text with the reduced trace, the readers of
the compile counters, and a traced run of the script at tiny size."""
import json
import pathlib
import time
from types import SimpleNamespace as NS

import pytest

from bench import harness, scopes, trace
from bench.tests.tiny import make_root

DATA = pathlib.Path(__file__).resolve().parent / "data"
KERNELS = ("kron_chain_fwd", "kron_chain_bwd", "kron_stage_grad")

HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %sine.0 = f32[8]{0} sine(%param_0.1), metadata={op_name="jit(step)/kronscope.model.ffn/sin"}
}

%body (p.1: (f32[8])) -> (f32[8]) {
  %p.1 = (f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p.1), index=0
  %dynamic-slice_reduce_fusion.16 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation
  ROOT %tuple.2 = (f32[8]{0}) tuple(%dynamic-slice_reduce_fusion.16)
}

%cond (p.2: (f32[8])) -> pred[] {
  %p.2 = (f32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %kron_chain_fwd.3 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(kronscope.model.ffn)/kronscope.op/kronscope.program/kronscope.stage/pallas_call"}
  %reshape.2 = f32[8]{0} reshape(%kron_chain_fwd.3), metadata={op_name="jit(step)/jvp(kronscope.model.ffn)/kronscope.op/kronscope.program/kronscope.stage/reshape"}
  %bitcast.7 = f32[8]{0} bitcast(%reshape.2)
  %copy = f32[8]{0} copy(%bitcast.7)
  %fusion.1 = f32[8]{0} fusion(%copy), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(kronscope.model.attn))/dot_general;kronscope.model.ffn/add"}
  %add.4 = f32[8]{0} add(%fusion.1, %x.1), metadata={op_name="jit(step)/kronscope.optim.update/add"}
  %gather.1 = f32[8]{0} gather(%add.4), metadata={op_name="jit(step)/jvp()/gather"}
  %tuple.181 = (f32[8]{0}) tuple(%x.1, %reshape.2)
  %while.60 = (f32[8]{0}) while(%tuple.181), condition=%cond, body=%body
  %dot.3 = f32[8]{0} dot(%x.1, %x.1), metadata={op_name="jit(step)/kronscope.op/shard_map/kronscope.round/kronscope.stage/jit(_chain_xla)/kronscope.round/kronscope.stage/dot_general"}
  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x.1)
  ROOT %copy-done = f32[8]{0} copy-done(%copy-start)
}
"""


def test_op_scopes_reads_each_instructions_chain():
    s = scopes.op_scopes(HLO)
    assert s["kron_chain_fwd.3"] == "model.ffn/op/program/stage"
    assert s["sine.0"] == "model.ffn"  # inside a fused computation: no trace event
    assert s["fusion.1"] == "model.attn"  # merged names: the first, whole one
    assert s["add.4"] == "optim.update"
    assert s["gather.1"] == "" and s["x.1"] == ""
    # inserted by XLA, no metadata: the chain of the value copied
    assert s["copy"] == s["bitcast.7"] == "model.ffn/op/program/stage"
    assert s["copy-done"] == s["copy-start"] == ""  # a copy of the parameter x
    # a loop XLA made to move the stage's output in pieces: the first scoped
    # operand of its input, and its body the loop's
    assert s["tuple.181"] == s["while.60"] == "model.ffn/op/program/stage"
    assert s["dynamic-slice_reduce_fusion.16"] == s["p.1"] == "model.ffn/op/program/stage"
    assert s["dot.3"] == "op/round/stage"  # a scope counts once in a chain
    assert scopes.innermost(s["copy"]) == "stage" and scopes.innermost("") == ""


def test_op_scopes_on_a_cpu_compiled_gradient():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import engine

    op = engine.KronOp((4, 4), (4, 4))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 16), jnp.float32)
    fs = tuple(jnp.asarray(rng.randn(4, 4), jnp.float32) for _ in range(2))
    ct = jnp.asarray(rng.randn(16, 16), jnp.float32)

    def loss(x, fs):
        y = op(x, fs)
        return jnp.vdot(y, ct) + jnp.vdot(y, y)

    txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, fs).compile().as_text()
    chains = set(scopes.op_scopes(txt).values())
    assert "op/op_bwd/stage_grad" in chains
    assert "op/program/stage" in chains  # the forward, which y·y needs
    assert chains <= {"", "op", "op/program", "op/program/stage", "op/op_bwd",
                      "op/op_bwd/stage_grad", "op/op_bwd/stage"}


US = 1000.0


def _summary(device_ops):
    """``trace.reduce`` of a hand-made trace: one chip per list of ops."""
    ev = lambda n, s, d: NS(name=n, start_ns=s * US, duration_ns=d * US)  # noqa: E731
    planes = [NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev(trace.WINDOW_SPAN, 0, 100)])])]
    for i, ops in enumerate(device_ops):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[NS(name=trace.OPS_LINE, events=[
            ev(f"%{n} = f32[8] op(%x)", s, d) for n, s, d in ops])]))
    return trace.reduce(NS(planes=planes), KERNELS)


OPS = [("kron_chain_fwd.3", 0, 10), ("reshape.2", 10, 6), ("copy", 16, 2),
       ("fusion.1", 20, 10), ("add.4", 30, 5), ("gather.1", 40, 4), ("while.9", 50, 20),
       ("copy-done", 55, 1)]


def test_table_sums_to_the_chips_self_time():
    s = _summary([OPS, OPS[:4]])
    tables = scopes.table(s, scopes.op_scopes(HLO), KERNELS)
    for d, t in zip(s.devices, tables):
        assert sum(t.values()) == pytest.approx(sum(d.ops.values()), rel=1e-12)
        assert sum(t.values()) == pytest.approx(d.busy_s, rel=1e-12)  # nothing overlaps
    assert tables[0][("model.ffn/op/program/stage", "kernel")] == pytest.approx(10e-6)
    assert tables[0][("model.ffn/op/program/stage", "other")] == pytest.approx(8e-6)
    assert tables[0][("", "other")] == pytest.approx(4e-6 + 19e-6 + 1e-6)  # gather, while, copy


def test_read_gives_each_metric_per_call():
    s = _summary([OPS])
    got = scopes.read(s, HLO, calls=2, kernel_names=KERNELS)
    assert got["emit.stage_glue_ms"] == pytest.approx(8e-6 / 2 * 1e3)  # reshape + copy
    assert got["model.ffn_ms"] == pytest.approx(18e-6 / 2 * 1e3)  # with its stage
    assert got["model.attn_ms"] == pytest.approx(10e-6 / 2 * 1e3)
    assert got["optim.update_ms"] == pytest.approx(5e-6 / 2 * 1e3)
    assert got["device.unscoped_pct"] == pytest.approx(100 * 24 / 57)
    # a scope with nothing of its own reads 0; one the program lacks, nothing
    assert got["engine.op_glue_ms"] == got["mesh.round_glue_ms"] == 0.0
    assert "model.head_ms" not in got


def test_read_gives_nothing_for_a_program_without_scopes():
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.splitlines())
    assert "kronscope" not in bare
    assert scopes.read(_summary([OPS]), bare, calls=2, kernel_names=KERNELS) == {}


def _run(**counters):
    return NS(counters=counters)


@pytest.mark.parametrize("metric", ["setup.compile_s", "setup.trace_s"])
def test_setup_readers_read_the_programs_compile_counters(metric, monkeypatch):
    from repro.runtime import compile_cache

    read = harness.load_reader(harness.BENCH, metric)
    totals = {"compile.trace_s": 2.5, "compile.backend_s": 4.0, "compile.cache_load_s": 1.5}
    monkeypatch.setattr(compile_cache, "totals", lambda: dict(totals))
    assert read(_run()) == {"setup.compile_s": 5.5, "setup.trace_s": 2.5}[metric]
    monkeypatch.setattr(compile_cache, "totals", dict)  # counted nothing
    assert read(_run()) is None
    monkeypatch.delattr(compile_cache, "totals")  # a program without the counters
    assert read(_run()) is None


def test_the_script_makes_a_traced_run_and_keeps_what_it_joined(tmp_path, monkeypatch, capsys):
    """At tiny size on the CPU, whose trace has no TPU planes: the reduction
    is stood in for, everything else is the harness's own traced run."""
    root = make_root(tmp_path / "root")
    hand_made = _summary([OPS])
    monkeypatch.setattr(trace, "reduce_dir", lambda d, kernel_names=(): hand_made)
    monkeypatch.setattr(trace, "find_xplane", lambda d: str(DATA / "gp26-fwd.xplane.pb"))
    keep = tmp_path / "keep"
    load_driver, reduce_dir = harness.load_driver, trace.reduce_dir
    rc = scopes.main(["--workload", "gp26-fwd", "--seed", "3", "--seconds", "0.2",
                      "--keep", str(keep)], t0=time.perf_counter(), root=root,
                     require_tpu=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and set(out["scopes"]) == {"metrics", "table", "top_ops", "busy_s"}
    assert "setup.compile_s" in out["metrics"] and "setup.trace_s" in out["metrics"]
    hlo = (keep / "gp26-fwd.hlo.txt").read_text()
    assert "kronscope.stage" in hlo and "source_line" not in hlo
    assert (keep / "gp26-fwd.xplane.pb").stat().st_size > 0
    assert harness.load_driver is load_driver and trace.reduce_dir is reduce_dir  # restored


def test_recorded_chip_trace_by_scope():
    """Fifteen ``gp26-fwd`` calls traced on one v5e, with the text of the
    executable that ran: every op joins the program's scopes, and the
    relayouts of the kernels' outputs are the stage's, as on the chip."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "gp26-fwd-scoped.xplane.pb"))
    hlo = (DATA / "gp26-fwd-scoped.hlo.txt").read_text()
    s = trace.reduce(pd, KERNELS)
    calls = sum(1 for n, _, _ in trace.host_spans(pd) if n == harness.CALL_SPAN)
    assert calls == 15
    ops = s.devices[0].ops
    assert set(ops) <= set(scopes.op_scopes(hlo))  # every op ran is in the text
    chains = scopes.op_scopes(hlo)
    # the two relayouts after the kernels' (1, 16, 256, 65536) outputs and
    # the layout copy of the last one: 55% of the chip's time, the stage's
    for op in ("reshape.2", "reshape.5", "copy"):
        assert chains[op] == "op/program/stage"
    relayouts = ops["reshape.2"] + ops["reshape.5"] + ops["copy"]
    assert relayouts / s.busy_s == pytest.approx(0.616, abs=0.01)
    tables = scopes.table(s, chains, KERNELS)
    assert sum(tables[0].values()) == pytest.approx(s.busy_s, rel=1e-9)
    got = scopes.read(s, hlo, calls, KERNELS)
    assert got["emit.stage_glue_ms"] == pytest.approx(30.66, abs=0.01)
    assert got["emit.stage_glue_ms"] == pytest.approx(s.mean("other_s") / calls * 1e3, rel=1e-6)
    assert got["engine.op_glue_ms"] == 0.0
    assert got["device.unscoped_pct"] < 0.01
    assert set(got) == {"emit.stage_glue_ms", "engine.op_glue_ms", "device.unscoped_pct"}
