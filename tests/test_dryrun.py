"""Dry-run machinery test on a small (2x4) mesh in a subprocess.

Validates the full path — build_cell -> jit(in/out shardings) -> lower ->
compile -> trip-weighted roofline record — without the 512-device
production mesh (exercised by launch/dryrun.py itself; its 66/66 log is
in experiments/).
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import repro.launch.mesh as mesh_mod

def small_mesh(*, multi_pod=False):
    assert not multi_pod
    return make_mesh((2, 4), ("data", "model"))

mesh_mod.make_production_mesh = small_mesh
from repro.launch import dryrun
from repro.runtime.sharding import make_mesh
rec = dryrun.run_cell("mamba2_130m", "decode_32k", False, None)
assert rec["chips"] == 8
assert rec["per_device"]["hlo_flops"] > 0
assert rec["per_device"]["hlo_bytes"] > 0
assert rec["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")
assert rec["fits_hbm"]
print("DRYRUN-TEST-OK", json.dumps(rec["roofline"]["dominant"]))
"""


@pytest.mark.slow
def test_dryrun_cell_on_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "DRYRUN-TEST-OK" in proc.stdout
