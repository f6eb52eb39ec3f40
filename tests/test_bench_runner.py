"""The benchmark driver refuses host-mesh rehearsals under a TPU backend."""
import jax

from benchmarks import run


def test_host_mesh_rehearsals_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert run.refused_on_tpu(run.ALL) == [
        n for n in run.ALL if n in run.HOST_MESH_REHEARSALS
    ]
    assert run.refused_on_tpu(["fig9", "tab5"]) == []


def test_host_mesh_rehearsals_run_on_cpu():
    assert jax.default_backend() == "cpu"
    assert run.refused_on_tpu(run.ALL) == []
