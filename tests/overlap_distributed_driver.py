"""Multi-device driver for the slab-pipelined distributed rounds (PR 10).

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(set by tests/test_distributed.py) so the parent pytest process keeps its
single-device view.  Prints 'OK <name>' per passing check; exits nonzero on
failure.

Checks, per the acceptance criteria:
  * the slabbed schedule is BITWISE identical (fwd and grads) to the serial
    schedule on both mesh runners — shared factors (single spine) and
    per-sample factors (batched spine) — at n_slabs in {2, 4};
  * compiled-HLO pin: the slabbed schedule emits exactly
    ``rounds * n_slabs`` all-to-alls, the serial schedule stays at ONE per
    round, and a non-divisor request clamps to the largest row divisor;
  * comm accounting under slabbing: the per-slab telemetry gauges sum to
    the SAME ``comm_elems_per_device`` total as the serial schedule per
    round — no double count, no missing slab;
  * ``KronOp.cost()``'s overlap term (``comm_hidden_elems``) reconciles
    with the per-slab telemetry gauges through ``KronOp.profile()``;
  * the measured distributed tuner ranks slabbed vs serial candidates on
    the emitted program and persists the plan under the ``;gk=`` cache key
    (old cache entries without ``n_slabs`` still load).
"""
import json
import math
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import autotune  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    comm_elems_per_device,
    comm_hidden_elems,
    plan_rounds,
    run_batched_distributed_rounds,
    run_distributed_rounds,
    sharded_input,
    sharded_input_batched,
)
from repro.core.engine import KronOp  # noqa: E402
from repro.kernels.emit import effective_slabs  # noqa: E402
from repro.runtime import telemetry  # noqa: E402
from repro.runtime.hlo_analysis import collective_stats  # noqa: E402
from repro.runtime.sharding import make_mesh  # noqa: E402

G_M, G_K = 2, 4


def _bitwise(a, b) -> bool:
    return bool((np.asarray(a) == np.asarray(b)).all())


def main() -> None:
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 devices, got {len(devs)}"
    mesh = make_mesh((G_M, G_K), ("data", "model"))

    M, PS, QS = 16, (4, 4, 4), (4, 4, 4)
    K = math.prod(PS)
    rev_ps, rev_qs = list(reversed(PS)), list(reversed(QS))
    k_loc = K // G_K
    rounds = plan_rounds(k_loc, rev_ps, rev_qs, G_K)
    keys = jax.random.split(jax.random.PRNGKey(0), len(PS) + 2)

    # --- single spine (shared factors): bitwise fwd + grads ----------------
    x = jax.random.normal(keys[0], (M, K), jnp.float32)
    fs = tuple(
        jax.random.normal(k, (p, q), jnp.float32)
        for k, p, q in zip(keys[1:], PS, QS)
    )
    xs = sharded_input(x, mesh)

    def loss_single(x, fs, n):
        y = run_distributed_rounds(x, fs, mesh, n_slabs=n)
        return (y * jnp.cos(y)).sum()  # x-dependent cotangent

    y_ser = run_distributed_rounds(xs, fs, mesh)
    g_ser = jax.grad(loss_single, argnums=(0, 1))(xs, fs, 1)
    for n in (2, 4):
        y_n = run_distributed_rounds(xs, fs, mesh, n_slabs=n)
        assert _bitwise(y_n, y_ser), f"single fwd n_slabs={n} not bitwise"
        g_n = jax.grad(loss_single, argnums=(0, 1))(xs, fs, n)
        assert _bitwise(g_n[0], g_ser[0]), f"single dx n_slabs={n} not bitwise"
        for a, r in zip(g_n[1], g_ser[1]):
            assert _bitwise(a, r), f"single dF n_slabs={n} not bitwise"
        print(f"OK single-bitwise n_slabs={n}")

    # --- batched spine (per-sample factors): bitwise fwd + grads -----------
    B = 4
    xb = jax.random.normal(keys[0], (B, M, K), jnp.float32)
    fb = tuple(
        jax.random.normal(k, (B, p, q), jnp.float32)
        for k, p, q in zip(keys[1:], PS, QS)
    )
    xbs = sharded_input_batched(xb, mesh)

    def loss_batched(x, fs, n):
        y = run_batched_distributed_rounds(x, fs, mesh, t_b=2, n_slabs=n)
        return (y * jnp.cos(y)).sum()

    yb_ser = run_batched_distributed_rounds(xbs, fb, mesh, t_b=2)
    gb_ser = jax.grad(loss_batched, argnums=(0, 1))(xbs, fb, 1)
    for n in (2, 4):
        yb_n = run_batched_distributed_rounds(xbs, fb, mesh, t_b=2, n_slabs=n)
        assert _bitwise(yb_n, yb_ser), f"batched fwd n_slabs={n} not bitwise"
        gb_n = jax.grad(loss_batched, argnums=(0, 1))(xbs, fb, n)
        assert _bitwise(gb_n[0], gb_ser[0]), f"batched dx n_slabs={n}"
        for a, r in zip(gb_n[1], gb_ser[1]):
            assert _bitwise(a, r), f"batched dF n_slabs={n} not bitwise"
        print(f"OK batched-bitwise n_slabs={n}")

    # --- HLO pin: rounds * n_slabs all-to-alls slabbed, one per round serial
    def a2a_count(n):
        fn = jax.jit(
            lambda x, fs: run_distributed_rounds(x, fs, mesh, n_slabs=n)
        )
        st = collective_stats(fn.lower(xs, fs).compile().as_text())
        return st.count_by_op.get("all-to-all", 0), st.total_bytes

    c1, bytes_ser = a2a_count(1)
    assert c1 == len(rounds), (c1, rounds)
    for n in (2, 4):
        cn, bytes_n = a2a_count(n)
        assert cn == len(rounds) * n, (cn, len(rounds), n)
        # per-slab payloads sum to the serial total, in the HLO too
        assert bytes_n == bytes_ser, (bytes_n, bytes_ser)
    # non-divisor request clamps: m_loc = 8 rows, n=3 -> 2 slabs
    c3, _ = a2a_count(3)
    assert effective_slabs(M // G_M, 3) == 2
    assert c3 == len(rounds) * 2, c3
    print(f"OK hlo-pin serial={c1} slabbed={{2: {len(rounds) * 2}, "
          f"4: {len(rounds) * 4}}} clamp(3)->2")

    # --- comm accounting: per-slab gauges sum to the serial total ----------
    m_loc = M // G_M
    total = comm_elems_per_device(m_loc, k_loc, rev_ps, rev_qs, G_K)
    assert total == comm_elems_per_device(
        m_loc, k_loc, rev_ps, rev_qs, G_K, n_slabs=4
    ), "comm_elems_per_device must be slab-invariant"
    telemetry.configure()
    try:
        run_distributed_rounds(xs, fs, mesh, n_slabs=4)
        summary = telemetry.comm_summary()
        assert sorted(summary) == list(range(len(rounds))), summary
        observed = 0
        for k, rec in summary.items():
            assert len(rec["slabs"]) == 4, (k, rec)
            assert sum(rec["slabs"]) == rec["total"], (k, rec)
            observed += rec["total"]
        assert observed == total, (observed, total)
        hidden_pred = comm_hidden_elems(
            m_loc, k_loc, rev_ps, rev_qs, G_K, n_slabs=4
        )
        hidden_obs = sum(r["hidden"] for r in summary.values())
        assert hidden_obs == hidden_pred, (hidden_obs, hidden_pred)
        print(f"OK comm-accounting total={total} hidden={hidden_pred} "
              f"(gauges sum per slab, no double count)")
    finally:
        telemetry.disable()

    # --- KronOp: cost() overlap term reconciles through profile() ----------
    op = KronOp(PS, QS, mesh=mesh, n_slabs=2)
    y_op = op(xs, fs)
    assert _bitwise(y_op, y_ser), "KronOp slabbed fwd not bitwise vs serial"
    cost = op.cost(M)
    assert cost.n_slabs == 2 and cost.rounds == len(rounds)
    assert cost.comm_elems_per_device == total
    assert cost.comm_hidden_elems == comm_hidden_elems(
        m_loc, k_loc, rev_ps, rev_qs, G_K, n_slabs=2
    )
    assert 0 < cost.comm_hidden_elems < cost.comm_elems_per_device
    assert cost.critical_path_s > 0
    telemetry.configure()
    try:
        op(xs, fs)  # records the per-slab gauges for this schedule
        report = op.profile(x, fs, warmup=0, iters=1)
        comm = report["comm"]
        assert comm["n_slabs"] == 2 and comm["hidden_elems"] > 0
        assert comm["telemetry_hidden_elems"] == comm["hidden_elems"], comm
        print(f"OK cost-telemetry-reconcile hidden={comm['hidden_elems']}")
    finally:
        telemetry.disable()

    # auto stays serial on latency-dominated (small) problems: the default
    # schedule — and every existing HLO pin — is unchanged.
    op_auto = KronOp(PS, QS, mesh=mesh)
    assert op_auto._resolve_n_slabs(m_loc) == 1
    fn_auto = jax.jit(lambda x, fs: op_auto(x, fs))
    st = collective_stats(fn_auto.lower(xs, fs).compile().as_text())
    assert st.count_by_op.get("all-to-all", 0) == len(rounds)
    print("OK auto-serial-small")

    # --- measured tuner ranks slabbed vs serial on the emitted program -----
    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "plans.json")
        prob = autotune.KronProblem(m_loc, PS, QS)
        plan = autotune.make_batched_plan(
            prob, B, shared_factors=False, tune="measure", g_k=G_K,
            cache_path=cache, mesh=mesh,
        )
        assert plan.n_slabs >= 1
        with open(cache) as fh:
            entries = json.load(fh)["entries"]
        gk_keys = [k for k in entries if k.endswith(f";gk={G_K}")]
        assert gk_keys, f"measured dist plan not cached under ;gk=: {entries}"
        # old entries (no n_slabs field) still load as serial
        d = autotune.plan_to_json(plan)
        d.pop("n_slabs")
        assert autotune.plan_from_json(d).n_slabs == 1
        # second resolve is a cache hit returning the same schedule
        plan2 = autotune.make_batched_plan(
            prob, B, shared_factors=False, tune="measure", g_k=G_K,
            cache_path=cache, mesh=mesh,
        )
        assert plan2.n_slabs == plan.n_slabs and plan2.t_b == plan.t_b
        print(f"OK measured-tuner n_slabs={plan.n_slabs} t_b={plan.t_b} "
              f"cached={gk_keys[0].split(';')[-1]}")

    print("ALL-OK")


if __name__ == "__main__":
    sys.exit(main())
