"""Multi-device chaos driver: fault-injected distributed Kron-Matmul (PR 6).

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(set by tests/test_distributed.py).  Prints 'OK <name>' per passing check;
exits nonzero on failure.

Checks, per the acceptance criteria:
  * ``chaos.inject("round_chain")`` forces the per-factor VMEM fallback in
    ``distributed.py::_local_multiply_round`` (previously only reachable by
    accident): the degraded result is BITWISE-identical to the unfaulted
    distributed reference, the compiled HLO still has exactly ONE all-to-all
    per relocation round (the fallback is strictly local), and the typed
    error is recorded in guard health;
  * ``chaos.inject("collective")`` fails the relocation itself: the KronOp
    mesh ladder degrades to local execution, records ``CollectiveError``,
    and still matches the unfaulted mesh result;
  * ``chaos.inject("slab_collective")`` fails one slab's all_to_all inside
    a pipelined round (PR 10): the three-rung ladder degrades slabbed ->
    serial rounds with BITWISE recovery (the serial schedule is immune to
    the slab site), and with the serial relocation failing too it degrades
    the rest of the way to local execution.
"""
import math
import os
import sys
import warnings

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import engine  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    kron_matmul_batched_distributed,
    plan_rounds,
    sharded_input_batched,
)
from repro.runtime import chaos, guard  # noqa: E402
from repro.runtime.hlo_analysis import collective_stats  # noqa: E402
from repro.runtime.sharding import make_mesh  # noqa: E402

G_M, G_K = 2, 4
B, M, PS, QS = 8, 8, (4, 4, 4), (4, 4, 4)


def _mk(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(PS) + 1)
    x = jax.random.normal(keys[0], (B, M, math.prod(PS)), jnp.float32)
    fs = tuple(
        jax.random.normal(k, (B, p, q), jnp.float32)
        for k, p, q in zip(keys[1:], PS, QS)
    )
    return x, fs


def main() -> None:
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 devices, got {len(devs)}"
    mesh = make_mesh((G_M, G_K), ("data", "model"))
    x, fs = _mk(seed=3)
    xs = sharded_input_batched(x, mesh)
    rounds = plan_rounds(
        math.prod(PS) // G_K, list(reversed(PS)), list(reversed(QS)), G_K
    )

    # --- round_chain chaos: the per-factor VMEM fallback, on purpose -------
    ref = kron_matmul_batched_distributed(xs, fs, mesh, shared_factors=False)
    guard.reset_health()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", guard.GuardWarning)
        with chaos.inject("round_chain") as specs:
            got = kron_matmul_batched_distributed(
                xs, fs, mesh, shared_factors=False
            )
    assert specs[0].fired >= len(rounds), (specs[0].fired, rounds)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    events = guard.health_report()["events"]
    assert events.get("round_per_factor", 0) >= len(rounds), events
    assert events.get("round_per_factor:VmemOverflowError", 0) >= 1, events
    print(f"OK round-chain-fallback bitwise rounds={len(rounds)}")

    # --- degraded rounds still pay ONE collective per round ----------------
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", guard.GuardWarning)
        with chaos.inject("round_chain"):
            st = collective_stats(
                jax.jit(
                    lambda x, fs: kron_matmul_batched_distributed(
                        x, fs, mesh, shared_factors=False
                    )
                ).lower(xs, fs).compile().as_text()
            )
    assert st.count_by_op.get("all-to-all", 0) == len(rounds), (
        f"degraded path must keep one all-to-all per round "
        f"({len(rounds)} rounds), got {st.count_by_op}"
    )
    print(f"OK collective-count degraded={st.count_by_op.get('all-to-all')}")

    # --- collective chaos: the mesh ladder degrades to local execution -----
    op = engine.kron_op_for(
        PS, QS, batch=B, m=M, shared_factors=False, mesh=mesh
    )
    mesh_ref = op(xs, fs)
    guard.reset_health()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", guard.GuardWarning)
        with chaos.inject("collective:times=1"):
            got_local = op(xs, fs)
    np.testing.assert_allclose(
        np.asarray(mesh_ref), np.asarray(got_local), rtol=1e-5, atol=1e-5
    )
    entries = [(k, h) for k, h in guard.health_entries() if k[0] == "mesh"]
    assert entries, "mesh ladder recorded no health entry"
    [(key, h)] = entries
    assert h.errors.get("CollectiveError") == 1, h.errors
    assert h.degraded_calls == 1 and h.calls == 1, h.summary()
    assert "guard[" in op.describe(), op.describe()
    # injection exhausted: the next call runs the mesh rung again, cleanly
    got_back = op(xs, fs)
    np.testing.assert_array_equal(np.asarray(mesh_ref), np.asarray(got_back))
    assert guard.health(key).consecutive == 0
    print("OK mesh-ladder-local-fallback")

    # --- slab_collective chaos: slabbed -> serial rounds, bitwise ----------
    from repro.core.distributed import sharded_input

    x1 = jax.random.normal(jax.random.PRNGKey(17), (M, math.prod(PS)))
    f1 = tuple(
        jax.random.normal(k, (p, q), jnp.float32)
        for k, p, q in zip(jax.random.split(jax.random.PRNGKey(19), len(PS)),
                           PS, QS)
    )
    x1s = sharded_input(x1, mesh)
    op_slab = engine.KronOp(PS, QS, mesh=mesh, n_slabs=2)
    slab_ref = op_slab(x1s, f1)
    guard.reset_health()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", guard.GuardWarning)
        with chaos.inject("slab_collective:times=1") as specs:
            got_serial = op_slab(x1s, f1)
    assert specs[0].fired == 1, specs[0]
    # the serial-rounds rung is IMMUNE to the slab site: recovery is one
    # rung down, not local, and bitwise (slabbed == serial by construction)
    np.testing.assert_array_equal(np.asarray(slab_ref), np.asarray(got_serial))
    msgs = [str(w.message) for w in caught]
    assert any(
        "rung 0 (mesh-slabbed)" in m and "rung 1 (mesh-rounds)" in m
        for m in msgs
    ), msgs
    entries = [(k, h) for k, h in guard.health_entries() if k[0] == "mesh"]
    [(key, h)] = entries
    assert h.errors.get("CollectiveError") == 1, h.errors
    assert h.degraded_calls == 1 and h.calls == 1, h.summary()
    # injection exhausted: the slabbed rung runs cleanly again
    np.testing.assert_array_equal(
        np.asarray(slab_ref), np.asarray(op_slab(x1s, f1))
    )
    assert guard.health(key).consecutive == 0
    print("OK slab-ladder-serial-fallback bitwise")

    # --- slab + serial collectives both failing: all the way to local ------
    guard.reset_health()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", guard.GuardWarning)
        with chaos.inject("slab_collective:times=1,collective:times=1"):
            got_local2 = op_slab(x1s, f1)
    np.testing.assert_allclose(
        np.asarray(slab_ref), np.asarray(got_local2), rtol=1e-5, atol=1e-5
    )
    [(key, h)] = [(k, h) for k, h in guard.health_entries() if k[0] == "mesh"]
    assert h.errors.get("CollectiveError") == 2, h.errors
    assert h.degraded_calls == 1 and h.calls == 1, h.summary()
    print("OK slab-ladder-local-fallback")

    print("ALL-OK")


if __name__ == "__main__":
    sys.exit(main())
