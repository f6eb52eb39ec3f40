"""Unit tests for the sharding rules + HLO cost analyzer."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.runtime.hlo_analysis import collective_stats, shape_bytes
from repro.runtime.hlo_cost import analyze
from repro.runtime.sharding import cache_spec, param_spec
from repro.runtime.sharding import make_mesh


@pytest.fixture(scope="module")
def mesh():
    # single-device mesh still exercises the rule logic (sizes are 1)
    return make_mesh((1, 1), ("data", "model"))


def test_param_spec_roles(mesh):
    # matrices: (fsdp, tp) in / (tp, fsdp) out
    assert param_spec("stack/pos0/mixer/wq", (8, 64, 64), mesh) == P(None, "data", "model")
    assert param_spec("stack/pos0/mixer/wo", (8, 64, 64), mesh) == P(None, "model", "data")
    assert param_spec("stack/pos0/ffn/w2", (64, 64), mesh) == P("model", "data")
    # embed vocab-over-TP
    assert param_spec("embed", (512, 64), mesh) == P("model", None)
    # KronLinear factors replicated
    assert param_spec("stack/pos0/ffn/w1/factors/0", (8, 8), mesh) == P(None, None)
    # norms replicated
    assert param_spec("final_norm", (64,), mesh) == P(None)


def test_param_spec_moe_expert_vs_tp(mesh):
    big = make_mesh((1, 1), ("data", "model"))
    # E divisible by tp (1) -> expert parallel
    assert param_spec("ffn/ew1", (4, 8, 16), big) == P("model", "data", None)


def test_param_spec_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    # dims of size 7 can't shard over axes of size 1? size-1 axes divide
    # everything; rules still apply. Use the path where dim % size != 0 by
    # constructing spec directly via _fit semantics: with 1-device axes all
    # divisible — assert shape-length consistency instead.
    spec = param_spec("stack/pos0/mixer/wq", (3, 7, 5), mesh)
    assert len(spec) == 3


def test_cache_spec_batch_vs_seq_sharding(mesh):
    # batch shardable -> batch-major
    assert cache_spec("stack/pos0/k", (2, 4, 128, 8, 64), mesh, batch=4) == P(
        None, "data", None, None, "model"
    )
    assert cache_spec("stack/pos0/pos", (2, 128), mesh, batch=4) == P(None, None)
    # The B=1 sequence-parallel branch needs a multi-device axis to
    # differentiate (on a size-1 mesh everything divides); it is exercised
    # end-to-end by the jamba/mamba2 long_500k dry-run cells (66/66 log).


def test_shape_bytes():
    assert shape_bytes("f32[2,3]") == 24
    assert shape_bytes("(f32[2,3]{1,0}, bf16[4])") == 24 + 8
    assert shape_bytes("pred[10]") == 10
    assert shape_bytes("token[]") == 0


def test_collective_stats_parsing():
    hlo = """
ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(%a), replica_groups={}
  %ag = f32[16]{0} all-gather(%ar), dimensions={0}
  ROOT %out = f32[8]{0} slice(%ag), slice={[0:8]}
}
"""
    st = collective_stats(hlo)
    assert st.bytes_by_op["all-reduce"] == 32
    assert st.bytes_by_op["all-gather"] == 64
    assert st.total_count == 2


def test_hlo_cost_trip_weighting():
    """The analyzer weights while bodies by known_trip_count (the bug in
    compiled.cost_analysis() it exists to fix).  Run hermetically in a
    subprocess: suite-global jax config (x64 from other modules) changes
    the compiled module shape."""
    import pathlib
    import subprocess
    import sys

    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.hlo_cost import analyze\n"
        "w = jnp.zeros((32, 32))\n"
        "def f(x):\n"
        "    def body(c, _):\n"
        "        return c @ w, None\n"
        "    return jax.lax.scan(body, x, None, length=7)[0]\n"
        "lowered = jax.jit(f).lower(jnp.zeros((32, 32)))\n"
        "txt = lowered.compile().as_text()\n"
        "c = analyze(txt)\n"
        "assert c.dot_flops == 7 * 2 * 32**3, c.dot_flops\n"
        "raw = lowered.compile().cost_analysis()\n"
        "assert raw['flops'] < 2 * 2 * 32**3, raw['flops']  # ~1 iter, not 7\n"
        "print('TRIP-OK')\n"
    )
    import os

    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "TRIP-OK" in proc.stdout


def test_hlo_cost_no_loops_matches_xla():
    x = jnp.zeros((64, 64), jnp.float32)
    txt = jax.jit(lambda a: a @ a).lower(x).compile().as_text()
    c = analyze(txt)
    assert c.dot_flops == 2 * 64**3
