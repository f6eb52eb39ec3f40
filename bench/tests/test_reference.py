"""The plain references against independent computations at small sizes,
and against the program where the program is the thing compared."""
import dataclasses
import json
import math

import numpy as np
import pytest

from bench import harness
from bench.reference import kron as kref
from bench.reference import lm as lref
from bench.reference import numerics


def _kron_problem(m=6, ps=(2, 3, 4), qs=(3, 2, 5), seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((m, math.prod(ps))).astype(np.float32)
    fs = tuple(rng.standard_normal((p, q)).astype(np.float32) for p, q in zip(ps, qs))
    ct = rng.standard_normal((m, math.prod(qs))).astype(np.float32)
    return x, fs, ct


def _dense(fs):
    w = np.ones((1, 1))
    for f in fs:
        w = np.kron(w, f.astype(np.float64))
    return w


def test_kron_reference_against_the_dense_matrix():
    x, fs, ct = _kron_problem()
    w = _dense(fs)
    np.testing.assert_allclose(kref.forward(x, fs), x @ w, rtol=1e-5, atol=1e-5)
    dfs = [kref.factor_grad(i, x, fs, ct) for i in range(len(fs))]
    # dF_i by finite structure: d<x kron(F), ct>/dF_i via the dense matrix
    for i, f in enumerate(fs):
        num = np.zeros_like(f, dtype=np.float64)
        for a in range(f.shape[0]):
            for b in range(f.shape[1]):
                e = [g.astype(np.float64) for g in fs]
                e[i] = np.zeros_like(e[i])
                e[i][a, b] = 1.0
                num[a, b] = np.sum((x @ _dense(e)) * ct)
        np.testing.assert_allclose(dfs[i], num, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("keep", [1, 2])
def test_factor_grad_partial_sums_add_up(keep):
    x, fs, ct = _kron_problem()
    for i, f in enumerate(fs):
        parts = np.asarray(kref.factor_grad(i, x, fs, ct, keep=keep), np.float64)
        assert parts.ndim == keep + 3 and parts.shape[0] == x.shape[0]
        np.testing.assert_allclose(parts.reshape((-1,) + f.shape).sum(0),
                                   kref.factor_grad(i, x, fs, ct), rtol=1e-5, atol=1e-5)


def test_blocked_comparison_matches_one_block():
    x, fs, ct = _kron_problem(m=8)
    y = kref.forward(x, fs)
    dx = kref.forward(ct, tuple(f.T for f in fs))
    dfs = tuple(kref.factor_grad(i, x, fs, ct) for i in range(3))
    whole = kref.compare(x, fs, [(y * 1.001, dx, dfs)], ct=ct, rows=8)
    blocks = kref.compare(x, fs, [(y * 1.001, dx, dfs)], ct=ct, rows=2)
    for k in whole:
        assert blocks[k] == pytest.approx(whole[k], rel=1e-4, abs=1e-6)
    assert whole["y_err"] == pytest.approx(1e-3, rel=1e-3)


@pytest.mark.parametrize("mode", ["high", "fp8"])
def test_control_modes_lose_precision(mode):
    rng = np.random.default_rng(1)
    a = rng.standard_normal((64, 64)).astype(np.float32)
    b = rng.standard_normal((64, 64)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    err = lambda m: float(np.linalg.norm(  # noqa: E731
        np.asarray(numerics.einsum("ij,jk->ik", a, b, m)) - exact) / np.linalg.norm(exact))
    assert err("highest") < 1e-6
    assert err(mode) > 10 * err("highest")


def test_lm_reference_loss_matches_the_program_at_small_width():
    """Same weights, same rows: the reference's loss and gradients against
    the program's ``loss_fn`` run in float32."""
    import jax
    import jax.numpy as jnp
    from repro.train import loss_fn

    from bench.kinds.lm import Driver, make_params
    from bench.tests.tiny import TINY_CONFIGS

    cfg = json.loads((harness.BENCH / "configs" / "qwen3-4b-kronffn.json").read_text())
    cfg.update(TINY_CONFIGS["qwen3-4b-kronffn"])
    cell = dataclasses.make_dataclass("C", ["config", "config_name", "traffic", "chips"])(
        cfg, "tiny", json.loads((harness.BENCH / "traffic" / "train_b2_s2048.json").read_text()), 1)
    driver = Driver(cell, 0, jax.devices()[:1], log=lambda m: None)
    program = dataclasses.replace(driver.cfg, dtype="float32")
    params = make_params(driver._param_shapes(), jax.random.PRNGKey(3), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 17), 0, cfg["vocab_size"])
    tokens, labels = toks[:, :-1], toks[:, 1:]
    with jax.default_matmul_precision("highest"):
        (p_loss, _), p_grad = jax.value_and_grad(
            lambda p: loss_fn(program, p, tokens, labels), has_aux=True)(params)
    r_loss, r_grad = jax.value_and_grad(
        lambda p: lref.loss(cfg, p, tokens, labels))(params)
    assert float(p_loss) == pytest.approx(float(r_loss), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p_grad),
                            jax.tree.leaves(r_grad)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_high_control_keeps_its_three_passes_under_jit():
    """The low parts of ``high``'s operands survive compilation: three bf16
    passes read far closer than one."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    a, b = (rng.standard_normal((64, 64)).astype(np.float32) for _ in range(2))
    exact = a.astype(np.float64) @ b.astype(np.float64)
    bf = lambda x: np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))  # noqa: E731
    rel = lambda y: np.linalg.norm(np.asarray(y, np.float64) - exact) / np.linalg.norm(exact)  # noqa: E731
    high = jax.jit(lambda a, b: numerics.einsum("ij,jk->ik", a, b, "high"))(a, b)
    one_pass = bf(a).astype(np.float64) @ bf(b).astype(np.float64)
    assert rel(high) < rel(one_pass) / 50
