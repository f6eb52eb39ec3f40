"""Multi-device driver for BATCHED distributed Kron-Matmul tests (PR 3).

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(set by tests/test_distributed.py) so the parent pytest process keeps its
single-device view.  Prints 'OK <name>' per passing check; exits nonzero on
failure.

Checks, per the acceptance criteria:
  * shared- and per-sample-factor batched results match the LOOPED
    per-problem ``kron_matmul_distributed`` reference (fwd + grads) on a
    >= 4-device model axis;
  * the batched path emits exactly ONE all_to_all per relocation round for
    the whole batch (the looped path emits B per round), pinned via compiled
    HLO counts AND the batch-aware ``comm_elems_per_device`` accounting;
  * consumers: ``gp_train_epoch_batched(mesh=...)`` and the
    ``layers.kron_distributed`` scope agree with their local counterparts.
"""
import math
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.distributed import (  # noqa: E402
    comm_elems_per_device,
    kron_matmul_batched_distributed,
    kron_matmul_distributed,
    plan_rounds,
    sharded_input_batched,
)
from repro.runtime.hlo_analysis import collective_stats  # noqa: E402
from repro.runtime.sharding import make_mesh  # noqa: E402

G_M, G_K = 2, 4


def _mk(b, m, ps, qs, *, per_sample, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(ps) + 1)
    x = jax.random.normal(keys[0], (b, m, math.prod(ps)), jnp.float32)
    shape = (lambda p, q: (b, p, q)) if per_sample else (lambda p, q: (p, q))
    fs = tuple(
        jax.random.normal(k, shape(p, q), jnp.float32)
        for k, p, q in zip(keys[1:], ps, qs)
    )
    return x, fs


def _looped(x, fs, mesh, *, per_sample):
    """The per-problem reference the batched path replaces: one distributed
    dispatch per sample, reassembled with stack."""
    b = x.shape[0]
    return jnp.stack([
        kron_matmul_distributed(
            x[i], tuple(f[i] for f in fs) if per_sample else fs, mesh
        )
        for i in range(b)
    ])


def main() -> None:
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 devices, got {len(devs)}"
    mesh = make_mesh((G_M, G_K), ("data", "model"))

    cases = [
        (8, 8, (4, 4, 4), (4, 4, 4)),     # rounds [2, 1] on G_K=4
        (4, 4, (2, 2, 2, 2), (2, 2, 2, 2)),  # Q=2: G_K|Q^L forces L>=2
        (6, 4, (4, 2, 4), (4, 4, 2)),     # rectangular mix, B not a pow2
    ]

    # --- correctness: batched == looped per-problem reference (fwd) --------
    for b, m, ps, qs in cases:
        for per_sample in (False, True):
            x, fs = _mk(b, m, ps, qs, per_sample=per_sample, seed=hash((b, ps)) % 997)
            xs = sharded_input_batched(x, mesh)
            got = kron_matmul_batched_distributed(
                xs, fs, mesh, shared_factors=not per_sample
            )
            want = _looped(x, fs, mesh, per_sample=per_sample)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
            )
            mode = "per_sample" if per_sample else "shared"
            print(f"OK fwd {mode} b={b} m={m} ps={ps} qs={qs}")

    # --- correctness: grads (fwd + bwd through the collective) -------------
    b, m, ps, qs = 8, 8, (4, 4, 4), (4, 4, 4)
    for per_sample in (False, True):
        x, fs = _mk(b, m, ps, qs, per_sample=per_sample, seed=7)

        def loss_b(x, fs, per_sample=per_sample):
            y = kron_matmul_batched_distributed(
                x, fs, mesh, shared_factors=not per_sample
            )
            return (y * jnp.cos(y)).sum()  # x-dependent cotangent

        def loss_l(x, fs, per_sample=per_sample):
            y = _looped(x, fs, mesh, per_sample=per_sample)
            return (y * jnp.cos(y)).sum()

        gx, gf = jax.grad(loss_b, argnums=(0, 1))(x, fs)
        gx_r, gf_r = jax.grad(loss_l, argnums=(0, 1))(x, fs)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-4)
        for a, r in zip(gf, gf_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)
        print(f"OK grads {'per_sample' if per_sample else 'shared'}")

    # --- one collective per round for the WHOLE batch ----------------------
    b, m, ps, qs = 8, 8, (4, 4, 4), (4, 4, 4)
    x, fs = _mk(b, m, ps, qs, per_sample=True, seed=3)
    xs = sharded_input_batched(x, mesh)
    rev_ps, rev_qs = list(reversed(ps)), list(reversed(qs))
    k_loc = math.prod(ps) // G_K
    rounds = plan_rounds(k_loc, rev_ps, rev_qs, G_K)

    fn_b = jax.jit(lambda x, fs: kron_matmul_batched_distributed(
        x, fs, mesh, shared_factors=False))
    st_b = collective_stats(fn_b.lower(xs, fs).compile().as_text())
    assert st_b.count_by_op.get("all-to-all", 0) == len(rounds), (
        f"batched path must emit one all-to-all per round "
        f"({len(rounds)} rounds), got {st_b.count_by_op}"
    )
    fn_l = jax.jit(lambda x, fs: _looped(x, fs, mesh, per_sample=True))
    st_l = collective_stats(fn_l.lower(x, fs).compile().as_text())
    assert st_l.count_by_op.get("all-to-all", 0) == b * len(rounds), (
        f"looped reference should emit B collectives per round, "
        f"got {st_l.count_by_op}"
    )
    print(f"OK collective-count batched={len(rounds)} looped={b * len(rounds)}")

    # --- batch-aware analytic comm accounting ------------------------------
    m_loc = m // G_M
    per_problem = comm_elems_per_device(m_loc, k_loc, rev_ps, rev_qs, G_K)
    whole_batch = comm_elems_per_device(
        m_loc, k_loc, rev_ps, rev_qs, G_K, batch=b
    )
    assert whole_batch == b * per_problem, (whole_batch, per_problem)
    # HLO payloads scale the same way: bytes(batched) == B * bytes(one problem)
    bytes_one = collective_stats(
        jax.jit(lambda x, fs: kron_matmul_distributed(x, fs, mesh))
        .lower(x[0], tuple(f[0] for f in fs)).compile().as_text()
    ).total_bytes
    assert st_b.total_bytes == b * bytes_one, (st_b.total_bytes, bytes_one)
    print(f"OK comm-accounting elems/dev={whole_batch} "
          f"(= {b} x {per_problem}), hlo {st_b.total_bytes}B = {b} x {bytes_one}B")

    # --- consumer: gp_train_epoch_batched(mesh=...) ------------------------
    from repro.gp.ski import (
        BatchedKronKernel, KronKernel, gp_train_epoch_batched, rbf_kernel_1d,
    )

    grid = jnp.linspace(0.0, 1.0, 4)
    kb = 4
    kernels = [
        KronKernel((rbf_kernel_1d(grid, 0.1 + 0.1 * i),
                    rbf_kernel_1d(grid, 0.3),
                    rbf_kernel_1d(grid, 0.2)))
        for i in range(kb)
    ]
    bk = BatchedKronKernel.stack(kernels)
    v = jax.random.normal(jax.random.PRNGKey(5), (kb, 8, bk.dim), jnp.float32)
    sol_d, res_d = gp_train_epoch_batched(bk, v, cg_iters=5, mesh=mesh)
    sol_l, res_l = gp_train_epoch_batched(bk, v, cg_iters=5)
    np.testing.assert_allclose(np.asarray(sol_d), np.asarray(sol_l),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res_d), np.asarray(res_l),
                               rtol=1e-4, atol=1e-4)
    print("OK gp-batched-mesh")

    # --- consumer: layers.kron_distributed scope ---------------------------
    from repro.core.layers import (
        KronLinearSpec, kron_distributed, kron_linear_apply, kron_linear_init,
    )

    spec = KronLinearSpec((4, 4, 4), (4, 4, 4))
    params = kron_linear_init(jax.random.PRNGKey(9), spec)
    xb = jax.random.normal(jax.random.PRNGKey(11), (4, 8, spec.d_in))
    y_local = kron_linear_apply(params, xb)
    with kron_distributed(mesh):
        y_dist = kron_linear_apply(params, xb)
        st = collective_stats(
            jax.jit(lambda p, x: kron_linear_apply(p, x))
            .lower(params, xb).compile().as_text()
        )
    assert st.count_by_op.get("all-to-all", 0) >= 1, st.count_by_op
    np.testing.assert_allclose(np.asarray(y_dist), np.asarray(y_local),
                               rtol=1e-5, atol=1e-5)
    # fallback: a width the model axis cannot host stays local, no error
    xs_bad = jax.random.normal(jax.random.PRNGKey(12), (4, 8, 6))
    ps_bad = kron_linear_init(jax.random.PRNGKey(13), KronLinearSpec((3, 2), (3, 2)))
    with kron_distributed(mesh):
        y_bad = kron_linear_apply(ps_bad, xs_bad)
    np.testing.assert_allclose(
        np.asarray(y_bad), np.asarray(kron_linear_apply(ps_bad, xs_bad)),
        rtol=1e-5, atol=1e-5,
    )
    print("OK layers-distributed-scope")

    print("ALL-OK")


if __name__ == "__main__":
    sys.exit(main())
