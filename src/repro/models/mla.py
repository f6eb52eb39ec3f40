"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), training path.

Keys and values come from one low-rank latent per token:

    q                 = x Wq                      (S, H, dn + dr) = [q_nope | q_pe]
    [c | k_pe]        = x Wkv_a                   (S, r + dr)
    [k_nope | v]      = RMSNorm(c) Wkv_b          (S, H, dn + dv)

RoPE (YaRN-scaled where the configuration says) turns ``q_pe`` and the one
``k_pe`` that every head shares; scores are ``[q_nope|q_pe] . [k_nope|k_pe]``
times ``(dn + dr)^-0.5 * mscale(mscale_all_dim)^2``, then the causal softmax
core of ``attention.py`` and ``Wo``.  No ``q_lora_rank``: queries are one
projection.

One departure from the source: DeepSeek-V2 de-interleaves the rotary
halves of ``q_pe``/``k_pe`` before rotate-half.  On weights drawn at random
that is a fixed permutation of ``Wq``'s and ``Wkv_a``'s rotary columns, so
this path applies plain rotate-half (as ``common.apply_rope``), and so does
the benchmark's reference.

Serving (a latent KV cache, MLA decode) is not built: ``model.init_cache``,
``prefill`` and ``decode_step`` refuse an MLA configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import causal_attention
from .common import apply_rope, dense_init, rms_norm, rope_freqs, yarn_freqs, yarn_mscale
from .config import ModelConfig


def softmax_scale(cfg: ModelConfig) -> float:
    """The scores' scale: ``(dn + dr)^-0.5``, times YaRN's ``mscale^2``."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def rotary_freqs(cfg: ModelConfig) -> jax.Array:
    """(dr/2,) inverse frequencies of the rotary part."""
    if cfg.rope_scaling is None:
        return rope_freqs(cfg.qk_rope_head_dim, cfg.rope_theta)
    return yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling)


def mla_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * (dn + dr), dtype),
        "wkv_a": dense_init(ks[1], d, r + dr, dtype),
        "kv_norm": jnp.zeros((r,), dtype),
        "wkv_b": dense_init(ks[2], r, h * (dn + dv), dtype),
        "wo": dense_init(ks[3], h * dv, d, dtype),
    }


def mla_forward(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
                *, q_chunk: int = 1024) -> jax.Array:
    """Causal full-sequence latent attention: x (B, S, D) -> (B, S, D)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    freqs = rotary_freqs(cfg)
    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = x @ p["wkv_a"]
    c, k_pe = ckv[..., :r], ckv[..., r:]
    kv = (rms_norm(c, p["kv_norm"], cfg.norm_eps) @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, freqs)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta, freqs)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    out = causal_attention(cfg, q, k, v, positions, q_chunk=q_chunk,
                           denom=1.0 / softmax_scale(cfg))
    return out.reshape(b, s, h * dv).astype(x.dtype) @ p["wo"]


__all__ = ["mla_init", "mla_forward", "softmax_scale", "rotary_freqs"]
