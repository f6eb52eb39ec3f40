"""Mixture-of-Experts: token-choice top-k routing with capacity buckets.

Covers Mixtral (8e top-2), DeepSeek-MoE (2 shared + 64 routed top-6,
fine-grained) and Jamba (16e top-2, every other layer).

TPU-native formulation: instead of the (T, E, C) one-hot dispatch einsum
(O(T*E*C) memory) or a dense compute-all-experts pass (E/k x FLOPs waste),
tokens are ranked within their expert via an argsort, scattered into
(E, C, D) capacity buckets, processed with per-expert stacked-weight
einsums (``ecd,edf->ecf`` — MXU-friendly, expert axis shardable for expert
parallelism), and gathered back weighted by router probs.  Routing happens
per sequence (vmap over batch) so no collective crosses the batch axis.

Tokens beyond capacity are dropped (standard Switch-style accounting);
capacity_factor=1.25 default.  An auxiliary load-balancing loss is returned
for the trainer.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import dense_init
from .config import ModelConfig, MoEConfig
from .ffn import ffn_apply, ffn_init


def moe_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    mc = cfg.moe
    d, f, e = cfg.d_model, mc.d_expert, mc.n_experts
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "ew1": (jax.random.truncated_normal(ks[1], -2, 2, (e, d, f)) * std).astype(dtype),
        "ew3": (jax.random.truncated_normal(ks[2], -2, 2, (e, d, f)) * std).astype(dtype),
        "ew2": (jax.random.truncated_normal(ks[3], -2, 2, (e, f, d)) * (f ** -0.5)).astype(dtype),
    }
    if mc.n_shared:
        p["shared"] = ffn_init(ks[4], cfg, dtype, d_ff=mc.n_shared * f)
    return p


def _capacity(s: int, mc: MoEConfig) -> int:
    c = int(s * mc.top_k * mc.capacity_factor / mc.n_experts) + 1
    return min(max(8, -(-c // 8) * 8), s * mc.top_k)  # mult of 8, <= all slots


def _route_one_seq(x, router_logits, mc: MoEConfig, capacity: int):
    """x: (S, D); router_logits: (S, E) f32.  Returns (S, D) output + aux."""
    s, d = x.shape
    e, k = mc.n_experts, mc.top_k
    probs = jax.nn.softmax(router_logits, axis=-1)  # (S, E)
    top_p, top_i = jax.lax.top_k(probs, k)  # (S, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    e_flat = top_i.reshape(-1)  # (S*k,)
    w_flat = top_p.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(s), k)  # token of each slot

    # rank of each slot within its expert (stable by token order)
    order = jnp.argsort(e_flat, stable=True)  # (S*k,)
    sorted_e = e_flat[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))  # (E,)
    rank_sorted = jnp.arange(s * k) - seg_start[sorted_e]
    rank = jnp.zeros((s * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    keep = rank < capacity
    slot_e = jnp.where(keep, e_flat, 0)
    slot_c = jnp.where(keep, rank, 0)

    # dispatch as a GATHER, not a scatter: scatter the (tiny, int32) token
    # ids into the (E, C) index map, then gather rows of x by it.  XLA's
    # SPMD partitioner replicates large scatter updates (measured f32
    # all-reduces of the full (S*k, D) dispatch per layer, §Perf C3); the
    # index scatter is E*C*4 bytes, and gathers partition cleanly.
    src = jnp.full((e, capacity), -1, jnp.int32)
    src = src.at[slot_e, slot_c].set(
        jnp.where(keep, t_flat, -1).astype(jnp.int32), mode="drop"
    )
    return src, (slot_e, slot_c, w_flat, keep)


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (B, S, D) -> (y, aux_loss)."""
    mc = cfg.moe
    b, s, d = x.shape
    capacity = _capacity(s, mc)

    router_logits = (x.astype(jnp.float32) @ p["router"])  # (B, S, E)

    # Routing is vmapped but touches only int32 index maps; ALL big-tensor
    # movement is batched take_along_axis gathers with pinned shardings —
    # XLA's scatter partitioner replicates large updates (measured f32
    # all-reduces of the whole (S*k, D) dispatch per layer, §Perf C3),
    # while gathers partition cleanly.
    src, metas = jax.vmap(
        lambda xi, li: _route_one_seq(xi, li, mc, capacity)
    )(x, router_logits)  # src: (B, E, C) int32
    slot_e, slot_c, w_flat, keep = metas

    from ..runtime.sharding import constrain

    e_tp = None  # expert axis role: "tp" when expert-parallel applies
    from ..runtime.sharding import ambient_mesh, _axes, _size

    mesh = ambient_mesh()
    if mesh is not None:
        _, tp_name = _axes(mesh)
        if mc.n_experts % _size(mesh, tp_name) == 0:
            e_tp = "tp"

    e = mc.n_experts
    # dispatch: (B, E*C, D) gather from token-major x
    valid = src >= 0
    buckets = jnp.take_along_axis(
        x, jnp.clip(src.reshape(b, e * capacity), 0)[..., None], axis=1
    ).reshape(b, e, capacity, d)
    buckets = jnp.where(valid[..., None], buckets, jnp.zeros((), x.dtype))
    buckets = constrain(buckets, "batch", e_tp, None, None)

    act = jax.nn.silu if cfg.ffn_act == "silu" else partial(
        jax.nn.gelu, approximate=True
    )
    h = act(jnp.einsum("becd,edf->becf", buckets, p["ew1"])) * jnp.einsum(
        "becd,edf->becf", buckets, p["ew3"]
    )
    h = constrain(h, "batch", e_tp, None, "tp" if e_tp is None else None)
    buckets_out = jnp.einsum("becf,efd->becd", h, p["ew2"]).astype(x.dtype)
    buckets_out = constrain(buckets_out, "batch", e_tp, None, None)

    # combine: slot-major gather back + token-major reshape-sum (slots are
    # token-major by construction, so no scatter is ever needed)
    flat_idx = (slot_e * capacity + slot_c).astype(jnp.int32)  # (B, S*k)
    gathered = jnp.take_along_axis(
        buckets_out.reshape(b, e * capacity, d), flat_idx[..., None], axis=1
    )  # (B, S*k, D)
    gathered = constrain(gathered, "batch", None, None)
    contrib = gathered * jnp.where(keep, w_flat, 0.0)[..., None].astype(x.dtype)
    y = contrib.reshape(b, s, mc.top_k, d).sum(axis=2)

    # Switch-style load-balance aux: E * sum_e (frac_tokens_e * frac_prob_e)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top1 = jnp.argmax(router_logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, mc.n_experts, dtype=jnp.float32), axis=(0, 1)
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = mc.n_experts * jnp.sum(frac_tokens * frac_probs)

    if mc.n_shared:
        y = y + ffn_apply(cfg, p["shared"], x)
    return y, aux


__all__ = ["moe_init", "moe_apply"]
