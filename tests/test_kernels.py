"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle.

Shapes sweep the paper's regimes: small P (fusion territory), large P
(MXU-aligned), rectangular P!=Q, plus tile-edge cases where the block size
equals / divides the dims unevenly enough to exercise the grid.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.kron_fused import fused_kron_pallas, max_n_fused
from repro.kernels.kron_sliced import sliced_multiply_pallas
from repro.kernels.ref import fused_kron_ref, sliced_multiply_ref


def _mk(seed, m, k, p, q, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (m, k)).astype(dtype)
    f = jax.random.normal(k2, (p, q)).astype(dtype)
    return x, f


SLICED_SHAPES = [
    # (m, p, q, s)  with K = s*p
    (2, 2, 2, 2),
    (8, 8, 8, 64),
    (16, 8, 8, 8),
    (4, 16, 16, 16),
    (8, 32, 32, 4),
    (2, 64, 64, 2),
    (8, 128, 128, 1),
    (8, 4, 8, 16),     # Q > P (expanding)
    (8, 8, 4, 16),     # Q < P (contracting)
    (1, 8, 8, 512),    # M=1 long row (paper GP case M small)
]


@pytest.mark.parametrize("m,p,q,s", SLICED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sliced_kernel_matches_ref(m, p, q, s, dtype):
    x, f = _mk(0, m, s * p, p, q, dtype)
    got = sliced_multiply_pallas(x, f, interpret=True)
    want = sliced_multiply_ref(x, f)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


@pytest.mark.parametrize(
    "m,p,q,s,t_m,t_s,t_q",
    [
        (8, 8, 8, 64, 2, 16, 4),   # all three grid dims > 1
        (8, 8, 8, 64, 8, 64, 8),   # single block
        (4, 16, 8, 32, 2, 8, 2),   # rectangular + tiled
        (16, 4, 4, 16, 4, 4, 1),   # t_q = 1 edge
    ],
)
def test_sliced_kernel_tilings(m, p, q, s, t_m, t_s, t_q):
    x, f = _mk(1, m, s * p, p, q)
    got = sliced_multiply_pallas(x, f, t_m=t_m, t_s=t_s, t_q=t_q, interpret=True)
    want = sliced_multiply_ref(x, f)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_sliced_kernel_rejects_bad_tiles():
    x, f = _mk(2, 8, 64, 8, 8)
    with pytest.raises(ValueError):
        sliced_multiply_pallas(x, f, t_m=3, interpret=True)  # 8 % 3 != 0


FUSED_CASES = [
    # (m, ps, qs, t_m, t_k)   factors given in application order (F^N first)
    (2, (4, 4), (4, 4), 2, 16),
    (4, (8, 8), (8, 8), 2, 64),
    (2, (4, 4, 4), (4, 4, 4), 2, 64),
    (2, (2, 2, 2, 2), (2, 2, 2, 2), 2, 16),
    (4, (4, 8), (8, 4), 2, 32),        # rectangular chain
    (2, (8, 8), (8, 8), 2, None),      # t_k = full K
]


@pytest.mark.parametrize("m,ps,qs,t_m,t_k", FUSED_CASES)
def test_fused_kernel_matches_ref(m, ps, qs, t_m, t_k):
    kdim = math.prod(ps)
    keys = jax.random.split(jax.random.PRNGKey(3), len(ps) + 1)
    x = jax.random.normal(keys[0], (m, kdim), jnp.float32)
    factors_last_first = [
        jax.random.normal(k, (p, q), jnp.float32)
        for k, p, q in zip(keys[1:], ps, qs)
    ]
    got = fused_kron_pallas(x, *factors_last_first, t_m=t_m, t_k=t_k, interpret=True)
    # ref applies last factor of the problem first; factors_last_first[0] is
    # F^N, so the problem-order list is reversed(factors_last_first).
    want = fused_kron_ref(x, list(reversed(factors_last_first)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_fused_kernel_vmem_guard():
    x = jnp.zeros((8, 1 << 14), jnp.float32)
    f = jnp.zeros((2, 2), jnp.float32)
    with pytest.raises(ValueError):
        fused_kron_pallas(
            x, f, f, t_m=8, t_k=1 << 14, interpret=True, vmem_budget_elems=1024
        )


def test_max_n_fused_matches_paper_formula():
    # paper: N_fused = floor(log_P T_K)
    assert max_n_fused(128, 4) == 3   # 4^3=64 <=128, 4^4=256 no
    assert max_n_fused(512, 8) == 3
    assert max_n_fused(8, 8) == 1
    assert max_n_fused(7, 8) == 0


TRANSPOSED_SHAPES = [
    (2, 2, 2, 2),
    (8, 8, 8, 64),
    (4, 16, 8, 16),    # rectangular
    (8, 4, 8, 32),
    (1, 8, 8, 512),
]


@pytest.mark.parametrize("m,p,q,s", TRANSPOSED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sliced_t_kernel_matches_ref(m, p, q, s, dtype):
    """Backward kernel (beyond-paper): dX for one sliced multiply."""
    from repro.kernels.kron_sliced_t import sliced_multiply_t_pallas
    from repro.kernels.ref import sliced_multiply_t_ref

    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    dy = jax.random.normal(k1, (m, q * s)).astype(dtype)
    f = jax.random.normal(k2, (p, q)).astype(dtype)
    got = sliced_multiply_t_pallas(dy, f, interpret=True)
    want = sliced_multiply_t_ref(dy, f)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


@pytest.mark.parametrize(
    "t_m,t_s,t_q", [(2, 16, 4), (8, 64, 8), (4, 8, 2), (8, 64, 1)]
)
def test_sliced_t_kernel_q_accumulation(t_m, t_s, t_q):
    """Output blocks accumulate across the innermost Q-tile grid dim."""
    from repro.kernels.kron_sliced_t import sliced_multiply_t_pallas
    from repro.kernels.ref import sliced_multiply_t_ref

    m, p, q, s = 8, 8, 8, 64
    k1, k2 = jax.random.split(jax.random.PRNGKey(10))
    dy = jax.random.normal(k1, (m, q * s), jnp.float32)
    f = jax.random.normal(k2, (p, q), jnp.float32)
    got = sliced_multiply_t_pallas(dy, f, t_m=t_m, t_s=t_s, t_q=t_q, interpret=True)
    np.testing.assert_allclose(got, sliced_multiply_t_ref(dy, f), rtol=1e-5, atol=1e-5)


def test_forward_backward_kernel_roundtrip():
    """sliced_t(sliced(x, I_perm)) recovers x for orthonormal factors."""
    from repro.kernels.kron_sliced import sliced_multiply_pallas
    from repro.kernels.kron_sliced_t import sliced_multiply_t_pallas

    x = jax.random.normal(jax.random.PRNGKey(11), (4, 64), jnp.float32)
    # orthonormal F: F F^T = I, so the transposed op inverts the forward
    f = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(12), (8, 8)))[0]
    y = sliced_multiply_pallas(x, f, interpret=True)
    back = sliced_multiply_t_pallas(y, f, interpret=True)
    np.testing.assert_allclose(back, x, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_sliced_t_dispatch(backend):
    from repro.kernels.ref import sliced_multiply_t_ref

    k1, k2 = jax.random.split(jax.random.PRNGKey(13))
    dy = jax.random.normal(k1, (4, 128), jnp.float32)
    f = jax.random.normal(k2, (8, 8), jnp.float32)
    got = ops.sliced_multiply_t(dy, f, backend=backend)
    np.testing.assert_allclose(got, sliced_multiply_t_ref(dy, f), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_dispatch_both_backends(backend):
    x, f = _mk(4, 8, 128, 8, 8)
    got = ops.sliced_multiply(x, f, backend=backend)
    want = sliced_multiply_ref(x, f)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_fused_dispatch_both_backends(backend):
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (4, 64), jnp.float32)
    f1 = jax.random.normal(keys[1], (4, 4), jnp.float32)
    f2 = jax.random.normal(keys[2], (4, 4), jnp.float32)
    got = ops.fused_kron(x, [f1, f2], backend=backend, t_m=2, t_k=16)
    want = fused_kron_ref(x, [f2, f1])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Q-tiled fused forward + fused transposed / backward kernels
# ---------------------------------------------------------------------------


def _mk_chain(seed, m, ps, qs):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(ps) + 1)
    x = jax.random.normal(keys[0], (m, math.prod(ps)), jnp.float32)
    factors_last_first = [
        jax.random.normal(k, (p, q), jnp.float32)
        for k, p, q in zip(keys[1:], ps, qs)
    ]
    return x, factors_last_first


@pytest.mark.parametrize(
    "m,ps,qs,t_m,t_k,t_qs",
    [
        (4, (4, 4), (4, 4), 2, 16, (2, 2)),
        (4, (2, 2), (8, 8), 2, 4, (4, 2)),       # expanding chain, tiled Q
        (2, (4, 4, 4), (4, 4, 4), 2, 64, (2, 4, 1)),
        (4, (4, 8), (8, 4), 2, 32, (4, 2)),      # rectangular
    ],
)
def test_fused_kernel_q_tiling_matches_ref(m, ps, qs, t_m, t_k, t_qs):
    x, fls = _mk_chain(20, m, ps, qs)
    got = fused_kron_pallas(x, *fls, t_m=t_m, t_k=t_k, t_qs=t_qs, interpret=True)
    want = fused_kron_ref(x, list(reversed(fls)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_kernel_q_tiling_lifts_vmem_restriction():
    """Problems where t_m*t_k*growth exceeds the budget become legal by
    tiling Q (acceptance criterion for the Q-tile grid axis)."""
    x, fls = _mk_chain(21, 8, (2, 2), (16, 16))
    # The padded VMEM model: full Q needs ~2.7 MB, Q-tiles of 4 ~0.24 MB.
    from repro.kernels import emit

    def need(t_qs):
        return emit.chain_vmem_bytes(
            1, 8, 4, (2, 2), t_qs, direction="fwd", flat=False,
            in_bytes=4, out_bytes=4,
        )

    budget = 1 << 17  # elements, i.e. 512 KiB
    assert need((4, 4)) <= budget * 4 < need((16, 16))
    with pytest.raises(ValueError):
        fused_kron_pallas(x, *fls, t_m=8, t_k=4, interpret=True,
                          vmem_budget_elems=budget)
    got = fused_kron_pallas(x, *fls, t_m=8, t_k=4, t_qs=(4, 4), interpret=True,
                            vmem_budget_elems=budget)
    np.testing.assert_allclose(
        got, fused_kron_ref(x, list(reversed(fls))), rtol=1e-5, atol=1e-5
    )


FUSED_T_CASES = [
    (4, (4, 4), (4, 4), 2, 16, None),
    (4, (4, 4), (4, 4), 2, 16, (2, 2)),      # accumulation over Q-tiles
    (2, (4, 4, 4), (4, 4, 4), 2, 64, None),
    (4, (4, 8), (8, 4), 2, 32, (2, 2)),
    (8, (2, 2), (8, 8), 4, 4, (4, 2)),
]


@pytest.mark.parametrize("m,ps,qs,t_m,t_k,t_qs", FUSED_T_CASES)
def test_fused_t_kernel_matches_ref(m, ps, qs, t_m, t_k, t_qs):
    from repro.kernels.kron_fused_t import fused_kron_t_pallas
    from repro.kernels.ref import fused_kron_t_ref

    x, fls = _mk_chain(22, m, ps, qs)
    y = fused_kron_ref(x, list(reversed(fls)))
    dy = jax.random.normal(jax.random.PRNGKey(23), y.shape, jnp.float32)
    got = fused_kron_t_pallas(dy, *fls, t_m=t_m, t_k=t_k, t_qs=t_qs, interpret=True)
    # fused_kron_t_ref takes problem order (F^1 first == fls reversed)
    want = fused_kron_t_ref(dy, list(reversed(fls)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_t_is_vjp_of_fused():
    """fused_kron_t computes exactly the input cotangent of fused_kron."""
    from repro.kernels.kron_fused_t import fused_kron_t_pallas

    x, fls = _mk_chain(24, 4, (4, 4), (4, 4))
    f_fwd = lambda x: fused_kron_ref(x, list(reversed(fls)))
    y, vjp = jax.vjp(f_fwd, x)
    dy = jax.random.normal(jax.random.PRNGKey(25), y.shape, jnp.float32)
    (want,) = vjp(dy)
    got = fused_kron_t_pallas(dy, *fls, t_m=2, t_k=16, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize(
    "m,ps,qs,t_m,t_k",
    [
        (4, (4, 4), (4, 4), 2, 16),
        (2, (4, 4, 4), (4, 4, 4), 2, 64),
        (4, (4, 8), (8, 4), 2, 32),
    ],
)
def test_fused_bwd_kernel_matches_autodiff(m, ps, qs, t_m, t_k):
    """One-kernel stage backward (dx + all factor grads) vs autodiff oracle."""
    from repro.kernels.kron_fused_t import fused_kron_bwd_pallas

    x, fls = _mk_chain(26, m, ps, qs)
    y = fused_kron_ref(x, list(reversed(fls)))
    dy = jax.random.normal(jax.random.PRNGKey(27), y.shape, jnp.float32)

    def loss(x, fls):
        return (fused_kron_ref(x, list(reversed(fls))) * dy).sum()

    dx_want, dfs_want = jax.grad(loss, argnums=(0, 1))(x, fls)
    dx, dfs = fused_kron_bwd_pallas(x, dy, *fls, t_m=t_m, t_k=t_k, interpret=True)
    np.testing.assert_allclose(dx, dx_want, rtol=1e-4, atol=1e-4)
    for got, want in zip(dfs, dfs_want):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_fused_t_dispatch(backend):
    from repro.kernels.ref import fused_kron_t_ref

    x, fls = _mk_chain(28, 8, (4, 4), (4, 4))
    y = fused_kron_ref(x, list(reversed(fls)))
    dy = jax.random.normal(jax.random.PRNGKey(29), y.shape, jnp.float32)
    got = ops.fused_kron_t(dy, fls, backend=backend, t_m=2, t_k=16)
    np.testing.assert_allclose(
        got, fused_kron_t_ref(dy, list(reversed(fls))), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("m", [4, 32])  # 32 exercises the xla M-tiled scan
def test_ops_fused_bwd_dispatch(backend, m):
    x, fls = _mk_chain(30, m, (4, 4), (4, 4))
    y = fused_kron_ref(x, list(reversed(fls)))
    dy = jax.random.normal(jax.random.PRNGKey(31), y.shape, jnp.float32)

    def loss(x, fls):
        return (fused_kron_ref(x, list(reversed(fls))) * dy).sum()

    dx_want, dfs_want = jax.grad(loss, argnums=(0, 1))(x, fls)
    dx, dfs = ops.fused_kron_bwd(x, dy, fls, backend=backend, t_m=2, t_k=16)
    np.testing.assert_allclose(dx, dx_want, rtol=1e-4, atol=1e-4)
    for got, want in zip(dfs, dfs_want):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Legacy fused_kron* shim surface (StageProgram refactor): each wrapper warns
# ONCE per process and its numerics are the emitter path's bit for bit.
# ---------------------------------------------------------------------------


def test_legacy_fused_shims_warn_once_and_match_emitter():
    import warnings

    from repro.kernels import emit

    x, fls = _mk_chain(40, 8, (4, 4), (4, 4))
    y = fused_kron_ref(x, list(reversed(fls)))
    dy = jax.random.normal(jax.random.PRNGKey(41), y.shape, jnp.float32)
    xb = jnp.stack([x, x + 1])
    flsb = [jnp.stack([f, f * 0.5]) for f in fls]
    dyb = jnp.stack([dy, dy])

    ops._SHIM_WARNED.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        y1 = ops.fused_kron(x, fls, t_m=2, t_k=16)
        ops.fused_kron(x, fls, t_m=2, t_k=16)  # 2nd call: no 2nd warning
        y2 = ops.fused_kron_t(dy, fls, t_m=2, t_k=16)
        y3 = ops.fused_kron_bwd(x, dy, fls, t_m=2, t_k=16)
        y4 = ops.fused_kron_batched(xb, flsb, t_b=1, t_m=2, t_k=16)
        y5 = ops.fused_kron_t_batched(dyb, flsb, t_b=1, t_m=2, t_k=16)
        y6 = ops.fused_kron_bwd_batched(xb, dyb, flsb, t_b=1, t_m=2, t_k=16)
    dep = [d for d in w if issubclass(d.category, DeprecationWarning)]
    names = sorted(str(d.message).split()[0] for d in dep)
    assert names == sorted(
        f"kernels.ops.{n}" for n in (
            "fused_kron", "fused_kron_t", "fused_kron_bwd",
            "fused_kron_batched", "fused_kron_t_batched",
            "fused_kron_bwd_batched",
        )
    ), names  # one warning per entry point, not per call
    assert all("StageInstr" in str(d.message) for d in dep)

    # Numerical identity: the shim IS the emitter path.
    mk = lambda kind, t_b=None: emit.StageInstr(
        kind=kind, ps=(4, 4), qs=(4, 4), t_m=2, t_k=16, t_b=t_b
    )
    np.testing.assert_array_equal(
        np.asarray(y1), np.asarray(emit.run_stage(x, tuple(fls), mk(emit.MULTIPLY)))
    )
    np.testing.assert_array_equal(
        np.asarray(y2),
        np.asarray(emit.run_stage(dy, tuple(fls), mk(emit.TRANSPOSED_MULTIPLY))),
    )
    dx, dfs = emit.run_stage_grad(x, dy, tuple(fls), mk(emit.MULTIPLY))
    np.testing.assert_array_equal(np.asarray(y3[0]), np.asarray(dx))
    for a, b in zip(y3[1], dfs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(y4),
        np.asarray(emit.run_stage(xb, tuple(flsb), mk(emit.MULTIPLY, 1))),
    )
    np.testing.assert_array_equal(
        np.asarray(y5),
        np.asarray(
            emit.run_stage(dyb, tuple(flsb), mk(emit.TRANSPOSED_MULTIPLY, 1))
        ),
    )
    dxb, dfsb = emit.run_stage_grad(xb, dyb, tuple(flsb), mk(emit.MULTIPLY, 1))
    np.testing.assert_array_equal(np.asarray(y6[0]), np.asarray(dxb))
    for a, b in zip(y6[1], dfsb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
