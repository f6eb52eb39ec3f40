"""MoE routing unit tests vs a dense compute-all-experts oracle."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import MoEConfig, ModelConfig
from repro.models.moe import _capacity, moe_apply, moe_init


def _cfg(e=4, k=2, cf=2.0, n_shared=0):
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=0, vocab=32,
        moe=MoEConfig(n_experts=e, top_k=k, d_expert=8, n_shared=n_shared,
                      capacity_factor=cf),
        dtype="float32",
    )


def _oracle(cfg, p, x):
    """Dense oracle: y = sum over top-k experts of w_e * FFN_e(x)."""
    mc = cfg.moe
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, mc.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    # compute all experts densely
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["ew1"])) * jnp.einsum(
        "bsd,edf->bsef", x, p["ew3"]
    )
    all_out = jnp.einsum("bsef,efd->bsed", h, p["ew2"])  # (B,S,E,D)
    mask = jax.nn.one_hot(top_i, mc.n_experts)  # (B,S,k,E)
    w = jnp.einsum("bske,bsk->bse", mask, top_p)
    return jnp.einsum("bsed,bse->bsd", all_out, w)


def test_matches_dense_oracle_no_drops():
    cfg = _cfg(cf=2.0)  # capacity == S: nothing dropped
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    got, aux = moe_apply(cfg, p, x)
    want = _oracle(cfg, p, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


def test_drops_occur_with_tiny_capacity():
    cfg = _cfg(cf=0.1)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 16))
    got, _ = moe_apply(cfg, p, x)
    want = _oracle(cfg, p, x)
    # with cf=0.1 captured tokens differ from the oracle for at least one row
    assert not np.allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(got)))


def test_shared_experts_added():
    cfg = _cfg(n_shared=1)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
    got, _ = moe_apply(cfg, p, x)
    # shared expert contribution == plain FFN on x
    from repro.models.ffn import ffn_apply

    routed, _ = moe_apply(cfg, {**p, "shared": jax.tree.map(jnp.zeros_like, p["shared"])}, x)
    shared_only = ffn_apply(cfg, p["shared"], x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(routed + shared_only), rtol=1e-4, atol=1e-5
    )


def test_capacity_formula():
    mc = MoEConfig(n_experts=8, top_k=2, d_expert=4, capacity_factor=1.25)
    c = _capacity(1024, mc)
    assert c % 8 == 0 and c >= 1024 * 2 * 1.25 / 8
    assert _capacity(1, mc) == 2  # decode: min(8, s*k) slots

    mc_big = MoEConfig(n_experts=4, top_k=2, d_expert=4, capacity_factor=2.0)
    assert _capacity(16, mc_big) >= 16  # cf=E/k: capacity>=S, dropless


def test_grad_finite_through_routing():
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16))

    def loss(p):
        y, aux = moe_apply(cfg, p, x)
        return jnp.sum(y**2) + aux

    g = jax.grad(loss)(p)
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # router must receive gradient (via combine weights + aux loss)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0


# -- Kron experts, routing options, the drop rule ---------------------------


def _kron_cfg(**moe_over):
    cfg = _cfg(e=4, k=2, cf=2.0)
    return replace(cfg, kron_ffn=True, moe=replace(cfg.moe, **moe_over))


def _dense_experts(p):
    """Each expert's three projections as dense (E, d_in, d_out) matrices."""
    from repro.core.layers import kron_linear_materialize

    return {k: jax.vmap(lambda *f: kron_linear_materialize({"factors": f}))(
        *p[k]["factors"]) for k in ("ew1", "ew3", "ew2")}


def test_kron_experts_are_one_batched_kronop_per_projection():
    """The routed experts of a ``kron_ffn`` model: per-expert factors, run
    through ``kron_matmul_batched`` (one factor set per batch element)."""
    from repro.core.engine import kron_matmul_batched_p

    cfg = _kron_cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert [f.shape for f in p["ew1"]["factors"]] == [(4, 4, 4), (4, 4, 2)]
    assert [f.shape for f in p["ew2"]["factors"]] == [(4, 4, 4), (4, 2, 4)]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    jaxpr = str(jax.make_jaxpr(lambda p, x: moe_apply(cfg, p, x))(p, x))
    assert jaxpr.count(kron_matmul_batched_p.name) == 3


def test_kron_experts_match_the_materialised_dense_experts():
    """Forward and gradients: the batched Kron experts against the same
    experts as dense ``kron(F1, F2)`` matrices through the dense path."""
    cfg = _kron_cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    dense_cfg = replace(cfg, kron_ffn=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))

    def as_dense(p):
        return {**{k: v for k, v in p.items() if k not in ("ew1", "ew3", "ew2")},
                **_dense_experts(p)}

    got, aux = moe_apply(cfg, p, x)
    want, aux_d = moe_apply(dense_cfg, as_dense(p), x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(aux) == pytest.approx(float(aux_d))

    # the batched KronOp's factor gradients (``kron_stage_grad`` per
    # expert) against autodiff through the dense matrices
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    g = jax.grad(lambda p: jnp.vdot(moe_apply(cfg, p, x)[0], ct))(p)
    g_d = jax.grad(lambda p: jnp.vdot(moe_apply(dense_cfg, as_dense(p), x)[0], ct))(p)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g), jax.tree.leaves(g_d)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("norm, scale", [(True, 1.0), (False, 1.0), (False, 2.5)])
def test_routing_options_against_the_dense_oracle(norm, scale):
    """``norm_topk_prob`` off: the chosen scores as they are, times
    ``routed_scaling_factor`` (DeepSeek-V2); on: renormalised to 1."""
    cfg = _cfg(cf=2.0)
    cfg = replace(cfg, moe=replace(cfg.moe, norm_topk_prob=norm,
                                   routed_scaling_factor=scale))
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, 2)
    w = top_p / top_p.sum(-1, keepdims=True) if norm else top_p * scale
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["ew1"])) * jnp.einsum(
        "bsd,edf->bsef", x, p["ew3"])
    all_out = jnp.einsum("bsef,efd->bsed", h, p["ew2"])
    want = jnp.einsum("bsed,bse->bsd", all_out,
                      jnp.einsum("bske,bsk->bse", jax.nn.one_hot(top_i, 4), w))
    got, _ = moe_apply(cfg, p, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_seq_aux_is_deepseeks_per_sequence_balance_loss():
    """``seq_aux``: per sequence, sum_e f_e P_e with f_e = count_e / (S k / E)
    and P_e the mean score, averaged over the batch."""
    from repro.models.moe import aux_loss, route

    mc = MoEConfig(n_experts=4, top_k=2, d_expert=8, seq_aux=True)
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 10, 4))
    probs, top_i, _ = route(mc, logits)
    want = []
    for b in range(3):
        counts = np.zeros(4)
        for t in range(10):
            for e in np.asarray(top_i[b, t]):
                counts[e] += 1
        f = counts / (10 * 2 / 4)
        want.append(np.sum(f * np.asarray(probs[b]).mean(0)))
    assert float(aux_loss(mc, logits, probs, top_i)) == pytest.approx(np.mean(want), rel=1e-5)
    # balanced choices and uniform scores read 1
    uniform = jnp.zeros((1, 4, 4))
    top = jnp.asarray([[[0, 1], [2, 3], [0, 1], [2, 3]]])
    assert float(aux_loss(mc, uniform, jax.nn.softmax(uniform), top)) == pytest.approx(1.0)


def test_aux_weight_comes_from_the_configuration():
    from repro.train import loss_fn
    from repro.models import model as M

    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=16, n_heads=2,
                      n_kv_heads=2, d_ff=0, vocab=32, vocab_pad_multiple=32,
                      moe=MoEConfig(n_experts=4, top_k=2, d_expert=8, aux_weight=0.001),
                      dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 32)
    loss, parts = loss_fn(cfg, params, toks, toks)
    assert float(parts["aux"]) > 0
    assert float(loss) == pytest.approx(float(parts["nll"] + 0.001 * parts["aux"]), rel=1e-6)


def test_drop_rule_keeps_each_experts_first_tokens_in_token_order():
    """Per sequence an expert's bucket holds the first C tokens that chose
    it, in token order; a dropped choice never evicts a kept one."""
    from repro.models.moe import _slots_one_seq

    mc = MoEConfig(n_experts=2, top_k=1, d_expert=4, capacity_factor=0.5)
    top_i = jnp.asarray([0] * 12 + [1] * 4)[:, None]  # 12 tokens choose expert 0
    capacity = 8
    src, (_, _, keep) = _slots_one_seq(top_i, mc, capacity)
    assert np.asarray(src[0]).tolist() == list(range(8))
    assert np.asarray(src[1]).tolist() == [12, 13, 14, 15, -1, -1, -1, -1]
    assert np.asarray(keep).tolist() == [True] * 8 + [False] * 4 + [True] * 4


def test_drops_never_evict_expert_zeros_first_token():
    """Dropped choices of later experts once aimed at bucket slot (0, 0)
    and could overwrite the token kept there."""
    from repro.models.moe import _slots_one_seq

    mc = MoEConfig(n_experts=2, top_k=1, d_expert=4)
    top_i = jnp.asarray([0] + [1] * 15)[:, None]  # expert 1 overflows
    src, _ = _slots_one_seq(top_i, mc, 8)
    assert int(src[0, 0]) == 0 and np.asarray(src[1]).tolist() == list(range(1, 9))
