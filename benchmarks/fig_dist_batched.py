"""Batched DISTRIBUTED Kron-Matmul benchmark (beyond paper, PR 3).

Compares ``kron_matmul_batched_distributed`` (ONE collective round per stage
for the whole batch) against the looped baseline a user would otherwise
write — a Python loop of B per-problem ``kron_matmul_distributed``
dispatches, each paying its own all_to_all rounds — on a forced multi-device
CPU host mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
mesh ``(2, 4)``), for both factor-sharing modes.

The measurement runs in a SUBPROCESS (same pattern as
tests/test_distributed.py): the device-count flag must be set before jax
initializes, and the parent benchmark harness keeps its single-device view.

Problem: B=8, M=32, (4,4)^3 per sample.  Emits ``BENCH_dist_batched.json``;
reproduced claim: batched >= 1.5x looped wall clock (the looped path pays
B x rounds collective latencies; the batched path pays rounds).  Also
records the compiled collective counts (batched == rounds, looped ==
B*rounds) and the batch-aware analytic comm volume
(``comm_elems_per_device(batch=B)``).  Methodology (block-interleaved
min-of-N timing) as EXPERIMENTS.md §Distributed-Batched.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from .util import bench_meta, csv_row
from repro.runtime.sharding import make_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT_JSON = ROOT / "BENCH_dist_batched.json"

N_DEVICES = 8
MESH_SHAPE = (2, 4)


# ---------------------------------------------------------------------------
# Child process: owns the forced multi-device jax runtime
# ---------------------------------------------------------------------------


def _child(quick: bool) -> None:
    import math
    import time

    import jax
    import jax.numpy as jnp

    from repro.core.distributed import (
        comm_elems_per_device,
        kron_matmul_batched_distributed,
        kron_matmul_distributed,
        plan_rounds,
        sharded_input_batched,
    )
    from repro.runtime.hlo_analysis import collective_stats

    b, m, ps, qs = 8, 32, (4, 4, 4), (4, 4, 4)
    iters = 12 if quick else 24
    g_m, g_k = MESH_SHAPE
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))

    def bench_pair(fn_a, fn_b, rounds_=6):
        """Block-interleaved min-of-N (same estimator as fig_batched)."""
        for _ in range(2):
            jax.block_until_ready(fn_a())
            jax.block_until_ready(fn_b())

        def block(fn, out):
            for _ in range(max(1, iters // rounds_)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                out.append(time.perf_counter() - t0)

        ta, tb = [], []
        for _ in range(rounds_):
            block(fn_a, ta)
            block(fn_b, tb)
        return min(ta), min(tb)

    rev_ps, rev_qs = list(reversed(ps)), list(reversed(qs))
    k_loc = math.prod(ps) // g_k
    n_rounds = len(plan_rounds(k_loc, rev_ps, rev_qs, g_k))
    record = {
        "problem": {"b": b, "m": m, "ps": list(ps), "qs": list(qs),
                    "dtype": "float32"},
        "mesh": {"devices": N_DEVICES, "data": g_m, "model": g_k,
                 "backend": jax.default_backend()},
        "rounds": n_rounds,
        "comm_elems_per_device": {
            "per_problem": comm_elems_per_device(
                m // g_m, k_loc, rev_ps, rev_qs, g_k
            ),
            "batched": comm_elems_per_device(
                m // g_m, k_loc, rev_ps, rev_qs, g_k, batch=b
            ),
        },
    }

    setups = {}
    for mode in ("shared", "per_sample"):
        per_sample = mode == "per_sample"
        keys = jax.random.split(jax.random.PRNGKey(17), len(ps) + 1)
        x = jax.random.normal(keys[0], (b, m, math.prod(ps)), jnp.float32)
        shape = (lambda p, q: (b, p, q)) if per_sample else (lambda p, q: (p, q))
        fs = tuple(
            jax.random.normal(k, shape(p, q), jnp.float32)
            for k, p, q in zip(keys[1:], ps, qs)
        )
        xs = sharded_input_batched(x, mesh)

        # Looped baseline: B per-problem distributed dispatches, reassembled.
        # Jitted as one program so the comparison is collectives + compute,
        # not Python dispatch overhead (which would only flatter the batched
        # side further).
        looped_fn = jax.jit(lambda x, fs, per_sample=per_sample: jnp.stack([
            kron_matmul_distributed(
                x[i], tuple(f[i] for f in fs) if per_sample else fs, mesh
            )
            for i in range(b)
        ]))
        batched_fn = jax.jit(
            lambda x, fs, per_sample=per_sample: kron_matmul_batched_distributed(
                x, fs, mesh, shared_factors=not per_sample
            )
        )

        counts = {
            side: collective_stats(
                fn.lower(xs, fs).compile().as_text()
            ).count_by_op.get("all-to-all", 0)
            for side, fn in (("looped", looped_fn), ("batched", batched_fn))
        }
        setups[mode] = (
            lambda x=xs, fs=fs, fn=looped_fn: fn(x, fs),
            lambda x=xs, fs=fs, fn=batched_fn: fn(x, fs),
            counts,
        )

    # Global warm-up before timing anything (see fig_batched).
    for looped, batched, _ in setups.values():
        jax.block_until_ready(looped())
        jax.block_until_ready(batched())

    for mode, (looped, batched, counts) in setups.items():
        t_loop, t_batch = bench_pair(looped, batched)
        record[mode] = {
            "looped_s": t_loop,
            "batched_s": t_batch,
            "speedup": t_loop / t_batch,
            "all_to_all": counts,
        }

    best = max(("shared", "per_sample"), key=lambda k: record[k]["speedup"])
    record["speedup"] = record[best]["speedup"]
    record["headline_mode"] = best
    record["meta"] = bench_meta()
    with open(OUT_JSON, "w") as f:
        json.dump(record, f, indent=1)


# ---------------------------------------------------------------------------
# Parent: spawn the multi-device child, report its artifact
# ---------------------------------------------------------------------------


def run(quick: bool = False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), str(ROOT), env.get("PYTHONPATH")) if p
    )
    cmd = [sys.executable, "-m", "benchmarks.fig_dist_batched", "--child"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(
        cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=1200
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fig_dist_batched child failed:\n{proc.stdout}\n{proc.stderr}"
        )
    with open(OUT_JSON) as f:
        record = json.load(f)
    for mode in ("shared", "per_sample"):
        r = record[mode]
        yield csv_row(
            "fig_dist_batched",
            mode=mode,
            b=record["problem"]["b"],
            m=record["problem"]["m"],
            mesh=f"{record['mesh']['data']}x{record['mesh']['model']}",
            looped_s=f"{r['looped_s']:.4f}",
            batched_s=f"{r['batched_s']:.4f}",
            speedup=f"{r['speedup']:.2f}",
            a2a_batched=r["all_to_all"]["batched"],
            a2a_looped=r["all_to_all"]["looped"],
        )
    yield csv_row(
        "fig_dist_batched",
        speedup=f"{record['speedup']:.2f}",
        headline_mode=record["headline_mode"],
        rounds=record["rounds"],
        artifact=os.fspath(OUT_JSON),
    )


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child(quick="--quick" in sys.argv)
    else:
        for row in run(quick="--quick" in sys.argv):
            print(row)
