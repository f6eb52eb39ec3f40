"""Shared model components: norms, RoPE, initializers, activations."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm: variance reduction in f32, tensor-wide math in the input
    dtype.  Keeping the (B,S,D)-wide intermediates bf16 matters at scale:
    XLA places the TP boundary collectives on whatever dtype the adjacent
    tensors carry — an all-f32 norm was measured to turn every residual
    psum/gather into f32 (2x collective bytes; EXPERIMENTS.md §Perf C1.it2)."""
    dt = x.dtype
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(dt)
    return x * inv * (1.0 + scale).astype(dt)


def dense_init(key: jax.Array, d_in: int, d_out: int, dtype) -> jax.Array:
    """Truncated-normal fan-in init (LeCun) — standard for LM projections."""
    std = d_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out)) * std).astype(
        dtype
    )


def embed_init(key: jax.Array, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(dim: int, theta: float, yarn) -> tuple[int, int]:
    """The first and last rotary pair of YaRN's ramp: the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""

    def dim_for(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(dim_for(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_for(yarn.beta_slow)), dim - 1)
    return low, high


def yarn_freqs(dim: int, theta: float, yarn) -> jax.Array:
    """(dim/2,) YaRN inverse frequencies: the pairs below the ramp keep their
    frequency, those above it are divided by ``factor``, linear between."""
    extra = rope_freqs(dim, theta)
    inter = extra / yarn.factor
    low, high = yarn_range(dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S); ``freqs``:
    (hd/2,) inverse frequencies (default: plain RoPE at ``theta``)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}[
        name
    ]


__all__ = ["rms_norm", "dense_init", "embed_init", "apply_rope", "rope_freqs",
           "yarn_freqs", "yarn_mscale", "yarn_range", "act_fn"]
