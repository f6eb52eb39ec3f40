"""Multi-head latent attention (DeepSeek-V2): YaRN's frequencies and scale
at the published numbers, the latent path against a plain per-head
computation, and serving's refusal of an MLA configuration."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mla
from repro.models import model as M
from repro.models.common import rope_freqs, yarn_freqs, yarn_mscale, yarn_range
from repro.models.config import ModelConfig, MoEConfig, YarnConfig

YARN = YarnConfig(factor=40, original_max_position_embeddings=4096, beta_fast=32,
                  beta_slow=1, mscale_all_dim=0.707)


def _cfg(**over):
    base = dict(name="mla-tiny", family="moe", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=4, d_ff=96, vocab=64, vocab_pad_multiple=32,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                rope_scaling=YARN, moe=MoEConfig(n_experts=4, top_k=2, d_expert=32),
                moe_skip_first=1, dtype="float32")
    return ModelConfig(**{**base, **over})


def test_yarn_at_deepseek_v2_lites_numbers():
    """dim 64, theta 1e4, factor 40 over 4096: the ramp runs over pairs
    10..23, the scale is (0.1 * 0.707 * ln 40 + 1)^2 / sqrt(192)."""
    assert yarn_range(64, 10000.0, YARN) == (10, 23)
    cfg = _cfg(qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert mla.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    assert yarn_mscale(40, 0.707) == pytest.approx(1 + 0.0707 * math.log(40))
    f, base = np.asarray(yarn_freqs(64, 10000.0, YARN)), np.asarray(rope_freqs(64, 10000.0))
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)  # below the ramp: kept
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)  # above: / factor
    r = (16 - 10) / 13
    assert f[16] == pytest.approx(base[16] / 40 * r + base[16] * (1 - r), rel=1e-6)


def test_mla_matches_a_plain_per_head_computation():
    cfg = _cfg()
    p = mla.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["kv_norm"] = jax.random.normal(jax.random.PRNGKey(5), p["kv_norm"].shape) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        got = mla.mla_forward(cfg, p, x, jnp.arange(12), q_chunk=4)
        h, dn, dr, dv, r = 4, 16, 8, 16, 32
        freqs = yarn_freqs(dr, cfg.rope_theta, YARN)
        ang = jnp.arange(12)[:, None] * freqs
        cos, sin = jnp.cos(ang), jnp.sin(ang)

        def rot(t):  # (S, dr)
            a, b = t[:, : dr // 2], t[:, dr // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        outs = []
        for bi in range(2):
            q = (x[bi] @ p["wq"]).reshape(12, h, dn + dr)
            ckv = x[bi] @ p["wkv_a"]
            c = ckv[:, :r]
            c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps) * (1 + p["kv_norm"])
            kv = (c @ p["wkv_b"]).reshape(12, h, dn + dv)
            k_pe = rot(ckv[:, r:])
            heads = []
            for hi in range(h):
                qh = jnp.concatenate([q[:, hi, :dn], rot(q[:, hi, dn:])], -1)
                kh = jnp.concatenate([kv[:, hi, :dn], k_pe], -1)
                s = qh @ kh.T * mla.softmax_scale(cfg)
                s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
                heads.append(jax.nn.softmax(s, -1) @ kv[:, hi, dn:])
            outs.append(jnp.concatenate(heads, -1) @ p["wo"])
    np.testing.assert_allclose(got, jnp.stack(outs), rtol=1e-4, atol=1e-5)


def test_mla_model_trains_and_differentiates():
    cfg = _cfg(kron_ffn=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params["prelude"][0]["mixer"]) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    g = jax.grad(lambda p: M.forward(cfg, p, toks)[0].sum())(params)
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert float(jnp.abs(g["stack"]["pos0"]["mixer"]["wkv_b"]).max()) > 0


@pytest.mark.parametrize("entry", ["init_cache", "prefill", "decode_step"])
def test_serving_refuses_an_mla_configuration(entry):
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 4), jnp.int32)
    calls = {"init_cache": lambda: M.init_cache(cfg, 1, 8),
             "prefill": lambda: M.prefill(cfg, params, toks, 8),
             "decode_step": lambda: M.decode_step(cfg, params, {}, toks[:, :1], 0)}
    with pytest.raises(NotImplementedError, match="latent attention"):
        calls[entry]()


def test_param_count_counts_the_latent_projections():
    cfg = _cfg(moe=None, moe_skip_first=0, n_layers=1, d_ff=0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree.leaves(params))
    norms = 2 * 64  # ln1, final_norm (not counted by param_count)
    assert cfg.param_count() == n - norms
