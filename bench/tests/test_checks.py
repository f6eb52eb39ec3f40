"""What decides ``correct``, at tiny size on the CPU: sound runs pass; the
control (the reference one precision step below, in the program's place)
fails; and a run whose timed path is broken underneath fails, once for
each fault its cell can have.  The limits are the cells' own
(``limits/<workload>.json``), or ``tiny.TINY_LIMITS`` where a number reads
differently at tiny size."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import control, harness
from bench.tests.tiny import make_root

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


def _run(root, workload):
    return harness.run(workload, SEED, 0.2, False, t0=time.perf_counter(), root=root,
                       require_tpu=False, log=lambda m: None)


def _fails(checks):
    return [k for k, c in checks.items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("workload", ["gp26-fwdbwd", "gp26-fwd", "qwen3-4b-train"])
def test_sound_run_passes_and_control_fails(tiny, workload):
    got = list(control.readings(workload, [SEED], [SEED], root=tiny,
                                require_tpu=False, log=lambda m: None))
    program, ctrl = got
    assert not _fails(program["checks"]), program
    assert _fails(ctrl["checks"]), ctrl


def test_half_batch_fails(tiny):
    (r,) = control.readings("qwen3-4b-train", [], [SEED], "half_batch", root=tiny,
                            require_tpu=False, log=lambda m: None)
    assert _fails(r["checks"]), r


@pytest.mark.parametrize("workload", ["gp26-fwdbwd", "gp26-fwd"])
def test_answer_altered_where_produced(tiny, workload, monkeypatch):
    from repro.core import engine

    call = engine.KronOp.__call__

    def altered(self, x, fs, *a, **kw):
        return call(self, x, fs, *a, **kw).at[1, 2].add(1.0)

    monkeypatch.setattr(engine.KronOp, "__call__", altered)
    out = _run(tiny, workload)
    assert not out["correct"] and "y_err" in _fails(out["checks"])


def _broken_step(kind):
    import repro.train

    make = repro.train.make_train_step

    def factory(cfg, opt_cfg, **kw):
        step = make(cfg, opt_cfg, **kw)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def half_batch(state, batch):
            half = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:half] for k, v in batch.items()})

        return {"unchanged": unchanged, "half_batch": half_batch}[kind]

    return factory


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step(tiny, fault, monkeypatch):
    import repro.train

    monkeypatch.setattr(repro.train, "make_train_step", _broken_step(fault))
    out = _run(tiny, "qwen3-4b-train")
    assert not out["correct"], out["checks"]


DIST = """
import json, pathlib, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import control, harness
from bench.tests.tiny import make_root
root = make_root(pathlib.Path(sys.argv[1]))
if sys.argv[2] == "no_exchange":
    jax.lax.all_to_all = lambda x, *a, **kw: x
if sys.argv[2] == "control":
    (r,) = control.readings("dist-64e4-fwd", [], [{seed}], root=root, require_tpu=False,
                            log=lambda m: None)
    print(json.dumps(r["checks"]))
else:
    out = harness.run("dist-64e4-fwd", {seed}, 0.2, False, t0=time.perf_counter(),
                      root=root, require_tpu=False, log=lambda m: None)
    print(json.dumps(out["checks"]))
"""


@pytest.mark.parametrize("case", ["sound", "no_exchange", "control"])
def test_dist_on_four_virtual_chips(tmp_path, case):
    code = DIST.format(root=str(harness.ROOT), src=str(harness.ROOT / "src"), seed=SEED)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path), case], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    checks = json.loads(p.stdout.strip().splitlines()[-1])
    assert bool(_fails(checks)) == (case != "sound"), checks
