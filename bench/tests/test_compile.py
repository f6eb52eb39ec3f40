"""Each cell's timed program, at its real size, compiled for a described
v5e:2x2 host (no chip needed): what the TPU's compiler refuses, or a
program that does not fit a chip's 16 GB, fails here and not on the chip.

This process's backend is the CPU, so the test steers the program's two
"am I on a TPU" switches (Pallas kernels, not interpret mode; the prekron
stage) to what the chip decides; the planner's peaks are then the v5e's.
A compile here is not a chip run.  ``memory_analysis()`` is printed for
each cell (run with ``-s`` to see it).
"""
from __future__ import annotations

import json

import pytest

from bench import harness

HBM_BYTES = 16e9
CELLS = [w["name"] for w in
         json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    harness.configure_jax()
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_compiles_for_v5e(topo, workload, monkeypatch):
    from repro.core import engine
    from repro.kernels import emit

    monkeypatch.setattr(emit, "_on_tpu", lambda: True)
    monkeypatch.setattr(engine, "_auto_prekron", lambda: True)
    cell = harness.resolve(workload)
    driver = harness.load_driver(cell.config["kind"])(
        cell, 0, topo.devices[: cell.chips], log=lambda m: None)
    compiled = driver.compile_abstract()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{workload}: arguments {mem.argument_size_in_bytes} output "
          f"{mem.output_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} -> {per_chip} bytes per chip")
    assert per_chip < HBM_BYTES
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "the emitter's Pallas kernels are not in the program"
