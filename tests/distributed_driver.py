"""Multi-device driver for distributed Kron-Matmul tests.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(set by tests/test_distributed.py) so the parent pytest process keeps its
single-device view.  Prints 'OK <name>' per passing check; exits nonzero on
failure.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import kron as K  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    comm_elems_per_device,
    kron_matmul_distributed,
    plan_rounds,
    sharded_input,
)


from repro.runtime.hlo_analysis import collective_bytes as _hlo_bytes  # noqa: E402
from repro.runtime.sharding import make_mesh  # noqa: E402


def collective_bytes(fn, *args) -> int:
    """Sum collective payload bytes in the compiled HLO."""
    return _hlo_bytes(jax.jit(fn).lower(*args).compile().as_text())


def main() -> None:
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 devices, got {len(devs)}"
    mesh = make_mesh((2, 4), ("data", "model"))

    # --- correctness: batched relocation == naive oracle -------------------
    cases = [
        (8, (2, 2, 2, 2), (2, 2, 2, 2)),   # P=Q=2, K=16, K_loc=4
        (4, (4, 4, 4), (4, 4, 4)),         # P=Q=4, K=64, K_loc=16
        (8, (2, 4, 2), (4, 2, 4)),         # rectangular mix
        (2, (8, 8), (8, 8)),
    ]
    import math

    for m, ps, qs in cases:
        key = jax.random.PRNGKey(hash((m, ps)) % 2**31)
        keys = jax.random.split(key, len(ps) + 1)
        x = jax.random.normal(keys[0], (m, math.prod(ps)), jnp.float32)
        factors = [
            jax.random.normal(k_, (p, q), jnp.float32)
            for k_, p, q in zip(keys[1:], ps, qs)
        ]
        want = K.kron_matmul_naive(x, factors)
        xs = sharded_input(x, mesh)
        got = kron_matmul_distributed(xs, factors, mesh)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
        got_pi = kron_matmul_distributed(xs, factors, mesh, per_iteration=True)
        np.testing.assert_allclose(np.asarray(got_pi), want, rtol=1e-4, atol=1e-4)
        print(f"OK correctness m={m} ps={ps} qs={qs}")

    # --- output sharding preserved -----------------------------------------
    xs = sharded_input(jnp.ones((8, 16)), mesh)
    y = kron_matmul_distributed(xs, [jnp.eye(2)] * 4, mesh)
    assert y.sharding.spec == P("data", "model"), y.sharding
    print("OK sharding")

    # --- round planning matches paper formula ------------------------------
    # K_loc=16, P=2: N_local = log_2 16 = 4 (all four factors in one round)
    assert plan_rounds(16, [2, 2, 2, 2], [2, 2, 2, 2], 4) == [4]
    # K_loc=4, P=2: rounds of 2
    assert plan_rounds(4, [2, 2, 2, 2], [2, 2, 2, 2], 4) == [2, 2]
    # G_K | Q^L constraint: Q=2, G_K=4 forces L>=2 even though P|K_loc at L=1
    assert plan_rounds(16, [2, 2], [2, 2], 4) == [2]
    print("OK round-planning")

    # --- comm volume: batched strictly less than per-iteration -------------
    # P=Q=4, K=256, G_K=4 -> K_loc=64: FastKron rounds [3,1] (N_local=log_4 64
    # =3) vs per-iteration [1,1,1,1]: 2 relocations vs 4.
    m, ps, qs = 8, (4, 4, 4, 4), (4, 4, 4, 4)
    x = jnp.ones((m, 256))
    factors = [jnp.eye(4) for _ in ps]
    xs = sharded_input(x, mesh)

    def run_batched(x_, fs):
        return kron_matmul_distributed(x_, fs, mesh)

    def run_periter(x_, fs):
        return kron_matmul_distributed(x_, fs, mesh, per_iteration=True)

    cb = collective_bytes(run_batched, xs, factors)
    cp = collective_bytes(run_periter, xs, factors)
    assert 0 < cb < cp, f"batched={cb} periter={cp}"
    # Analytic: per device per round sends M_loc*C*(G_K-1)/G_K elems.
    m_loc, k_loc = m // 2, 256 // 4
    analytic_batched = comm_elems_per_device(
        m_loc, k_loc, list(reversed(ps)), list(reversed(qs)), 4
    )
    analytic_periter = comm_elems_per_device(
        m_loc, k_loc, list(reversed(ps)), list(reversed(qs)), 4,
        rounds=plan_rounds(k_loc, list(reversed(ps)), list(reversed(qs)), 4, minimal=True),
    )
    assert analytic_batched < analytic_periter
    print(f"OK comm-volume batched={cb}B periter={cp}B "
          f"(analytic elems/dev {analytic_batched} vs {analytic_periter})")

    # --- G_M axis is communication-free (rows embarrassingly parallel) ------
    mesh_dp = make_mesh((8, 1), ("data", "model"))
    xs_dp = sharded_input(jnp.ones((8, 256)), mesh_dp)
    cb_dp = collective_bytes(lambda x_, fs: kron_matmul_distributed(x_, fs, mesh_dp),
                             xs_dp, factors)
    assert cb_dp == 0, f"expected no comm for G_K=1, got {cb_dp}"
    print("OK no-comm-on-data-axis")

    print("ALL-OK")


if __name__ == "__main__":
    main()
