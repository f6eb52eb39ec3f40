"""Figure 11: weak scaling of distributed Kron-Matmul, 1-16 "GPUs".

The paper's 16-V100 measurement becomes, on this CPU container, a
communication-volume comparison from the compiled HLO (hardware-
independent) plus a bandwidth model: FastKron's batched relocation
(N_local multiplies per round) vs the per-iteration baseline (CTF/DISTAL
communicate after EVERY factor).  Weak scaling: M grows with G, per-device
block constant (paper: P=64, N=4).

Runs in a subprocess with 16 fake devices so the parent process keeps its
single-device view.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from .util import csv_row

ICI_BW = 50e9  # bytes/s per link (same model as the roofline)

_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, math, sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as Pspec
from repro.core.distributed import kron_matmul_distributed
from repro.runtime.hlo_cost import analyze
from repro.runtime.sharding import make_mesh

P, N = (int(sys.argv[1]), int(sys.argv[2])) if len(sys.argv) > 2 else (64, 4)
quick = len(sys.argv) > 3 and sys.argv[3] == "quick"
out = []
for g in ([1, 4, 16] if quick else [1, 2, 4, 8, 16]):
    g_m = 1
    m = 4 * g          # weak scaling: rows grow with devices
    k = P ** N
    mesh = make_mesh((g_m, g), ("data", "model"),
                         devices=jax.devices()[: g_m * g])
    # dry lowering: ShapeDtypeStructs only, no allocation (paper sizes are
    # GPU-memory-scale; comm volume comes from the compiled HLO)
    xs = jax.ShapeDtypeStruct(
        (m, k), jnp.float32,
        sharding=NamedSharding(mesh, Pspec("data", "model")))
    fs = [jax.ShapeDtypeStruct((P, P), jnp.float32,
                               sharding=NamedSharding(mesh, Pspec()))
          for _ in range(N)]
    rec = {"g": g, "m": m}
    for name, per_it in [("fastkron", False), ("periter", True)]:
        fn = lambda x_, f_: kron_matmul_distributed(
            x_, f_, mesh, per_iteration=per_it)
        txt = jax.jit(fn).lower(xs, fs).compile().as_text()
        c = analyze(txt)
        rec[name + "_coll_bytes"] = c.total_collective_bytes
        rec[name + "_flops"] = c.dot_flops
    out.append(rec)
print(json.dumps(out))
"""


def run(quick: bool = False):
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    # paper sizes (P=64, N=4): lowering is allocation-free so the full size
    # compiles fine on CPU
    args = [sys.executable, "-c", _DRIVER, "64", "4"] + (["quick"] if quick else [])
    proc = subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = []
    for rec in data:
        fb, pb = rec["fastkron_coll_bytes"], rec["periter_coll_bytes"]
        rows.append(csv_row(
            "fig11",
            gpus=rec["g"],
            m=rec["m"],
            comm_bytes_fastkron=int(fb),
            comm_bytes_periter=int(pb),
            comm_reduction=f"{pb/max(fb,1):.2f}",
            modeled_comm_ms_fastkron=f"{fb/ICI_BW*1e3:.3f}",
            modeled_comm_ms_periter=f"{pb/ICI_BW*1e3:.3f}",
        ))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
