"""Comm/compute-overlapped distributed rounds benchmark (PR 10).

Times the slab-pipelined round schedule against the serial schedule on the
forced 8-device CPU host mesh (``(2, 4)`` = ``(data, model)``), at
``n_slabs`` in {1, 2, 4} on the single/shared spine, and records which
schedule the MEASURED distributed tuner picks for the per-sample batched
problem (``make_batched_plan(tune="measure", mesh=...)``).

The measurement runs in a SUBPROCESS (same pattern as fig_dist_batched):
the device-count flag must be set before jax initializes.

CAVEAT — host-mesh numbers UNDERSTATE the overlap win: the "collectives"
here are memcpys between host buffers, so there is almost no transfer time
for the pipeline to hide and the slabbed schedules mostly measure their own
launch overhead.  The reproduced claims are therefore (a) ``n_slabs=1`` is
within noise (<5%) of the serial schedule — the pipeline machinery is free
when unused — and (b) the compiled collective counts scale exactly as
``rounds * n_slabs`` while the total collective BYTES stay constant (the
per-slab payloads repartition, never duplicate, the serial payload).  On a
real ICI mesh the analytic model (``autotune._slab_schedule_seconds``)
predicts the crossover near ``A2A_LATENCY_S * ICI_BW`` (~100 KB) per-round
payloads; the measured tuner owns the final call.  Emits
``BENCH_dist_overlap.json``; methodology as EXPERIMENTS.md
§Distributed-Overlap.
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
OUT_JSON = ROOT / "BENCH_dist_overlap.json"

N_DEVICES = 8
MESH_SHAPE = (2, 4)
SLAB_COUNTS = (1, 2, 4)


# ---------------------------------------------------------------------------
# Child process: owns the forced multi-device jax runtime
# ---------------------------------------------------------------------------


def _child(quick: bool) -> None:
    import math
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import autotune
    from repro.core.distributed import (
        comm_elems_per_device,
        comm_hidden_elems,
        plan_rounds,
        run_distributed_rounds,
        sharded_input,
    )
    from repro.runtime.hlo_analysis import collective_stats

    # Full mode keeps the same m as quick and spends the extra budget on
    # timing iterations: m=1024 pushes the measured-tuner candidate sweep
    # past 20 minutes on the 2-vCPU CI host (8 fake devices share 2 cores),
    # and m=512 is already past the analytic break-even where the measured
    # tuner selects a slabbed schedule.
    m, ps, qs = 512, (4, 4, 4), (4, 4, 4)
    b_tuner = 8
    iters = 12 if quick else 24
    g_m, g_k = MESH_SHAPE
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))

    rev_ps, rev_qs = list(reversed(ps)), list(reversed(qs))
    k_loc = math.prod(ps) // g_k
    rounds = plan_rounds(k_loc, rev_ps, rev_qs, g_k)
    m_loc = m // g_m

    keys = jax.random.split(jax.random.PRNGKey(23), len(ps) + 1)
    x = jax.random.normal(keys[0], (m, math.prod(ps)), jnp.float32)
    fs = tuple(
        jax.random.normal(k, (p, q), jnp.float32)
        for k, p, q in zip(keys[1:], ps, qs)
    )
    xs = sharded_input(x, mesh)

    # One jitted program per schedule; "serial" is the default entry point
    # (no n_slabs argument at all), the others force the slab count.
    fns = {"serial": jax.jit(
        lambda x, fs: run_distributed_rounds(x, fs, mesh)
    )}
    for n in SLAB_COUNTS:
        fns[f"n{n}"] = jax.jit(
            lambda x, fs, n=n: run_distributed_rounds(x, fs, mesh, n_slabs=n)
        )

    a2a = {}
    nbytes = {}
    hlo = {}
    for name, fn in fns.items():
        hlo[name] = fn.lower(xs, fs).compile().as_text()
        st = collective_stats(hlo[name])
        a2a[name] = st.count_by_op.get("all-to-all", 0)
        nbytes[name] = st.total_bytes
    assert a2a["serial"] == len(rounds), a2a
    for n in SLAB_COUNTS:
        assert a2a[f"n{n}"] == len(rounds) * n, (a2a, rounds)
        assert nbytes[f"n{n}"] == nbytes["serial"], nbytes
    # n_slabs=1 IS the serial schedule: same traced body, same compiled
    # program — the "overhead when unused" claim is structural, not a
    # wall-clock coin flip (the timing below just corroborates it).
    n1_same_program = hlo["n1"] == hlo["serial"]

    # Block-interleaved min-of-N across all schedules (same estimator as
    # fig_dist_batched): each timing block revisits every schedule so drift
    # hits them equally.  One SAMPLE is ``reps`` back-to-back dispatches —
    # a single call is sub-millisecond here and dispatch jitter would
    # otherwise dominate the serial-vs-n1 comparison (identical programs).
    for fn in fns.values():
        jax.block_until_ready(fn(xs, fs))

    reps = 8
    best = {name: float("inf") for name in fns}
    for _ in range(6):
        for name, fn in fns.items():
            for _ in range(max(1, iters // 6)):
                t0 = time.perf_counter()
                for _ in range(reps):
                    y = fn(xs, fs)
                jax.block_until_ready(y)
                best[name] = min(
                    best[name], (time.perf_counter() - t0) / reps
                )

    schedules = {}
    for n in SLAB_COUNTS:
        schedules[str(n)] = {
            "time_s": best[f"n{n}"],
            "all_to_all": a2a[f"n{n}"],
            "collective_bytes": nbytes[f"n{n}"],
            "hidden_elems": comm_hidden_elems(
                m_loc, k_loc, rev_ps, rev_qs, g_k, n_slabs=n
            ),
        }
    # Byte-identical programs have 0 overhead by definition; the raw
    # timings stay in the record (schedules / serial_s) for the skeptical.
    overhead = (
        0.0 if n1_same_program else best["n1"] / best["serial"] - 1.0
    )
    fastest = min(SLAB_COUNTS, key=lambda n: best[f"n{n}"])

    # The measured distributed tuner's pick for the per-sample batched
    # problem (wall-clocked candidates on THIS mesh, fresh cache).
    import tempfile

    prob = autotune.KronProblem(m_loc, ps, qs)
    with tempfile.TemporaryDirectory() as td:
        plan = autotune.make_batched_plan(
            prob, b_tuner, shared_factors=False, tune="measure", g_k=g_k,
            cache_path=os.path.join(td, "plans.json"), mesh=mesh,
        )
    analytic_n = autotune.choose_n_slabs(
        prob, g_k, batch=b_tuner, dtype_bytes=4
    )

    record = {
        "problem": {"m": m, "ps": list(ps), "qs": list(qs),
                    "dtype": "float32"},
        "mesh": {"devices": N_DEVICES, "data": g_m, "model": g_k,
                 "backend": jax.default_backend()},
        "rounds": len(rounds),
        "comm_elems_per_device": comm_elems_per_device(
            m_loc, k_loc, rev_ps, rev_qs, g_k
        ),
        "serial_s": best["serial"],
        "schedules": schedules,
        "n1_overhead_vs_serial": overhead,
        "n1_same_program": n1_same_program,
        "fastest_n_slabs": fastest,
        "tuner": {
            "batch": b_tuner,
            "measured_n_slabs": plan.n_slabs,
            "measured_t_b": plan.t_b,
            "analytic_n_slabs": analytic_n,
        },
        "caveat": (
            "host mesh: collectives run at memcpy speed, so overlap has "
            "almost nothing to hide and these numbers UNDERSTATE the "
            "slabbed schedules vs a real ICI mesh (moduledoc)"
        ),
        "meta": bench_meta(),
    }
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
    cmd = [sys.executable, "-m", "benchmarks.fig_dist_overlap", "--child"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(
        cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=1200
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fig_dist_overlap child failed:\n{proc.stdout}\n{proc.stderr}"
        )
    with open(OUT_JSON) as f:
        record = json.load(f)
    for n, r in record["schedules"].items():
        yield csv_row(
            "fig_dist_overlap",
            n_slabs=n,
            m=record["problem"]["m"],
            mesh=f"{record['mesh']['data']}x{record['mesh']['model']}",
            time_s=f"{r['time_s']:.4f}",
            all_to_all=r["all_to_all"],
            hidden_elems=r["hidden_elems"],
        )
    yield csv_row(
        "fig_dist_overlap",
        serial_s=f"{record['serial_s']:.4f}",
        n1_overhead=f"{record['n1_overhead_vs_serial']:+.1%}",
        n1_same_program=record["n1_same_program"],
        fastest_n_slabs=record["fastest_n_slabs"],
        tuner_n_slabs=record["tuner"]["measured_n_slabs"],
        tuner_t_b=record["tuner"]["measured_t_b"],
        artifact=os.fspath(OUT_JSON),
    )


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child(quick="--quick" in sys.argv)
    else:
        for row in run(quick="--quick" in sys.argv):
            print(row)
