"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

No chip is needed: the installed TPU compiler compiles for a described
``v5e:2x2`` topology and refuses what the chip's compiler would refuse — a
block shape the tiling forbids, an in-kernel relayout Mosaic cannot lower,
more VMEM than a kernel may use.  Interpret mode sees none of these.

Each case takes the stages the planner emits for the chip
(``backend="pallas"``), tiles each as the emitter does on the chip
(``emit.stage_tiles``) and compiles its three kernels — the forward chain,
the transposed chain and the stage backward — asserting a
``tpu_custom_call`` in each compiled program.  The topology is described in
a fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import autotune
from repro.core.engine import KronOp
from repro.core.kron import KronProblem
from repro.core.layers import KronLinearSpec
from repro.kernels import emit


def _ffn(direction: str):
    """The qwen3-4b ``kron_ffn`` up (d_model -> d_ff) or down factors."""
    cfg = get_config("qwen3-4b")
    d_in, d_out = (
        (cfg.d_model, cfg.d_ff) if direction == "up" else (cfg.d_ff, cfg.d_model)
    )
    spec = KronLinearSpec.balanced(d_in, d_out, cfg.kron_factors)
    return tuple(spec.ps), tuple(spec.qs)


# (id, M, problem-order P dims, Q dims, dtype) — training rows are batch 4 x
# seq 512; Table 4 rows as in benchmarks/fig10.py.
CASES = [
    ("ffn_up", 2048, *_ffn("up"), jnp.float32),
    ("ffn_down", 2048, *_ffn("down"), jnp.float32),
    ("row15", 16, (8,) * 3, (8,) * 3, jnp.float32),
    ("row18", 1024, (4,) * 7, (4,) * 7, jnp.float32),
    ("row28", 16, (64,) * 3, (64,) * 3, jnp.float32),
    ("ffn_up_bf16", 2048, *_ffn("up"), jnp.bfloat16),
]
KERNELS = ("fwd", "bwd", "grad")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _stages(m, ps, qs, dtype):
    """(instr, stage-input columns) of every stage the chip would run."""
    itemsize = jnp.dtype(dtype).itemsize
    plan = autotune.make_plan(
        KronProblem(m, ps, qs), dtype_bytes=itemsize, backend="pallas"
    )
    cols, out = math.prod(ps), []
    for instr in autotune.lower(plan, ps, qs).instrs:
        out.append((instr, cols))
        cols = cols // instr.pprod * instr.qprod
    return out


def _kernel_factors(instr):
    """The (P, Q) factors the kernel chains: a prekron stage's product."""
    if instr.kind == emit.PREKRON:
        return ((instr.pprod, instr.qprod),)
    return tuple(zip(instr.ps, instr.qs))


def _compile(kernel, instr, m, cols, dtype, sharding):
    pqs = _kernel_factors(instr)
    shape = lambda *s: jax.ShapeDtypeStruct(s, dtype, sharding=sharding)  # noqa: E731
    fs = [shape(1, p, q) for p, q in pqs]
    out_cols = cols // instr.pprod * instr.qprod
    if kernel == "grad":
        tiles = emit.stage_tiles(instr, (m, cols), dtype, grad=True)
        assert tiles is not None, f"{instr.describe()}: no legal backward tiling"
        t_b, t_m, t_k = tiles
        fn = lambda x, dy, *fs: emit.grad_pallas(  # noqa: E731
            x, dy, *fs, t_b=t_b, t_m=t_m, t_k=t_k, interpret=False
        )
        args = (shape(1, m, cols), shape(1, m, out_cols), *fs)
    else:
        ins = instr if kernel == "fwd" else instr.transpose()
        rows_in = cols if kernel == "fwd" else out_cols
        tiles = emit.stage_tiles(ins, (m, rows_in), dtype)
        assert tiles is not None, f"{ins.describe()}: no legal tiling"
        t_b, t_m, t_k = tiles
        fn = lambda x, *fs: emit.chain_pallas(  # noqa: E731
            x, *fs, t_b=t_b, t_m=t_m, t_k=t_k, direction=ins.direction,
            interpret=False,
        )
        args = (shape(1, m, rows_in), *fs)
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize(
    "name,m,ps,qs,dtype", CASES, ids=[c[0] for c in CASES]
)
def test_main_path_kernels_compile_for_v5e(one_chip, kernel, name, m, ps, qs, dtype):
    seen = set()
    # Other test modules switch 64-bit mode on process-wide; Mosaic lowers
    # no 64-bit index arithmetic, and the chip runs with it off.
    with jax.enable_x64(False):
        for instr, cols in _stages(m, ps, qs, dtype):
            key = (instr.kind, _kernel_factors(instr), cols)
            if key in seen:  # identical stages compile to the identical kernel
                continue
            seen.add(key)
            hlo = _compile(kernel, instr, m, cols, dtype, one_chip)
            assert "tpu_custom_call" in hlo, f"{name} {kernel} {instr.describe()}"


# ---------------------------------------------------------------------------
# The y-side view in HBM: no relayout between Kron stages
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z0-9\-]*)\((.*?)\)")


def _instrs(hlo: str) -> dict:
    """name -> (opcode, element count of the result, operand names)."""
    out = {}
    for line in hlo.splitlines():
        hit = _INSTR.match(line)
        if hit is None:
            continue
        name, shape, opcode, operands = hit.groups()
        dims = re.match(r"\(?\w+\[([\d,]*)\]", shape)
        count = math.prod(int(d) for d in dims.group(1).split(",") if d) if dims else 0
        out[name] = (opcode, count, re.findall(r"%([\w.\-]+)", operands))
    return out


def _kron_programs(m, dtype, sharding):
    """The compiled forward, value_and_grad (the stage backward) and grad in
    X alone (the transposed chain) of a (16,)*4 KronOp planned for the chip:
    two prekron stages of 256x256, s_out = 256, K-tiled at ts = 128."""
    ps = (16,) * 4
    op = KronOp(ps, ps, m=m, backend="pallas", enable_prekron=True,
                dtype_bytes=jnp.dtype(dtype).itemsize)
    shape = lambda *s: jax.ShapeDtypeStruct(s, dtype, sharding=sharding)  # noqa: E731
    x, ct = shape(m, 16 ** 4), shape(m, 16 ** 4)
    fs = tuple(shape(16, 16) for _ in ps)

    def loss(x, fs, ct):
        return jnp.vdot(op(x, fs).astype(jnp.float32), ct.astype(jnp.float32))

    programs = {
        "fwd": (lambda x, fs: op(x, fs), (x, fs)),
        "value_and_grad": (jax.value_and_grad(loss, argnums=(0, 1)), (x, fs, ct)),
        "grad_x": (jax.grad(loss), (x, fs, ct)),
    }
    return op, {
        name: jax.jit(fn).lower(*args).compile().as_text()
        for name, (fn, args) in programs.items()
    }


@pytest.mark.parametrize(
    "m,dtype,view",
    [(16, jnp.float32, "bitcast"), (16, jnp.bfloat16, "bitcast"),
     (20, jnp.float32, "relayout")],
    ids=["m16", "m16_bf16", "m20"],
)
def test_kron_stages_pass_outputs_as_bitcasts(one_chip, monkeypatch, m, dtype, view):
    """Each K-tiled kernel reads and writes the flat (M, K) array's bytes:
    every (M, K)-sized kernel operand is a bitcast or a parameter, and no
    reshape, copy or transpose of that size is left in the program (a
    ``copy-start`` only moves an array between memory spaces).  M = 20 has
    no 8-row groups: its kernels keep the relayouted view and compile."""
    monkeypatch.setattr(emit, "_on_tpu", lambda: True)  # compile, not interpret
    with jax.enable_x64(False):
        op, hlos = _kron_programs(m, dtype, one_chip)
    assert {fwd.split(":")[-1] for _, fwd, _ in op.stage_executors()} == {view}
    size = m * 16 ** 4
    for name, hlo in hlos.items():
        instrs = _instrs(hlo)
        kernels = [i for i in instrs if i.startswith(emit.KERNEL_NAMES)]
        assert kernels, name
        if view == "relayout":
            continue
        for k in kernels:
            for operand in instrs[k][2]:
                opcode, count, _ = instrs[operand]
                if count == size:
                    assert opcode in ("bitcast", "parameter"), (name, k, operand, opcode)
        relayouts = [
            i for i, (opcode, count, _) in instrs.items()
            if opcode in ("reshape", "copy", "transpose") and count == size
        ]
        assert not relayouts, (name, relayouts)
