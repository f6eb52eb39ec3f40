"""The harness: files found by name, refusals, and a whole run at tiny size."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.tiny import make_root

ROOT = harness.ROOT


@pytest.fixture()
def tiny(tmp_path):
    return make_root(tmp_path)


def _run(root, workload, **kw):
    return harness.run(workload, 2**32 + 5, 0.2, False, t0=time.perf_counter(),
                       root=root, require_tpu=False, log=lambda m: None, **kw)


def test_every_cell_resolves_with_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(cell.bench_dir, m["name"]))
        for path in spec["paths"]:
            assert (ROOT / path).is_dir()


def test_dropped_in_files_are_found_by_name(tiny):
    bench = tiny / "bench"
    shutil.copy(bench / "traffic" / "fwd.json", bench / "traffic" / "fwd_again.json")
    (bench / "configs" / "kron-small.json").write_text(json.dumps(
        {"kind": "kron", "m": 8, "ps": [2, 4], "qs": [4, 2], "dtype": "float32"}))
    shutil.copy(bench / "limits" / "gp26-fwd.json", bench / "limits" / "small-fwd.json")
    (bench / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return run.calls / run.window_s\n")
    spec = json.loads((tiny / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "kron-small", "source": "test",
                            "file": "bench/configs/kron-small.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "small-fwd", "config": "kron-small",
                              "traffic": "fwd_again", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "calls_per_s", "unit": "1/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["small-fwd"]})
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(tiny, "small-fwd")
    assert out["correct"]
    assert set(out["metrics"]) == {"calls_per_s", "setup_s"}
    assert out["metrics"]["calls_per_s"]["value"] > 0


def test_unknown_names_are_refused(tiny):
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.resolve("no-such-cell", tiny)


def test_result_line_has_the_contract_keys(tiny):
    out = _run(tiny, "gp26-fwd")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"kron_call_ms", "setup_s"}
    assert set(out["checks"]) == {"y_err"}


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gp26-fwd", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_split_metric_shares_its_quantitys_reader():
    bench = ROOT / "bench"
    for name in ("device.idle_pct.kron", "device.idle_pct.train"):
        read = harness.load_reader(bench, name)
        assert read.__code__.co_filename.endswith("device.idle_pct.py")
    with pytest.raises(harness.BenchError, match="no reader"):
        harness.load_reader(bench, "no_such.metric")


class _Chip:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


STATS = {"bytes_limit": 16e9, "peak_bytes_in_use": 4e9}


@pytest.mark.parametrize("ahead_s, stats, out_bytes, temp_bytes, want", [
    (4.0, STATS, 2e9, 2e9, 3),     # memory binds: 0.8 of the 10 GB left, less 2 kept
    (4.0, STATS, 2e9, 9e9, 0),     # the temporaries leave no room
    (0.25, STATS, 1e3, 2e9, 3),    # time binds: 0.25 s of 0.1 s calls
    (0.0, STATS, 2e9, 0, 0),       # the traffic asks for none
    (4.0, None, 2e9, 0, 1),        # a backend that reports no memory
])
def test_queue_depth(ahead_s, stats, out_bytes, temp_bytes, want):
    chips = [_Chip(stats), _Chip(stats)]
    assert harness.queue_depth(chips, 0.1, ahead_s, out_bytes, reserve=out_bytes,
                               temp_bytes=temp_bytes) == want


@pytest.mark.parametrize("workload, module", [("gp26-fwdbwd", "kron"),
                                              ("qwen3-4b-train", "lm")])
def test_queued_calls_all_count_and_are_checked(tiny, workload, module, monkeypatch):
    import importlib

    monkeypatch.setattr(importlib.import_module(f"bench.kinds.{module}"),
                        "queue_depth", lambda *a, **k: 3)
    out = _run(tiny, workload)
    assert out["correct"] and out["attempted"] > 3 and out["failed"] == 0
