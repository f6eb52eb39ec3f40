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
for the trainer: Switch's over the batch, or with ``seq_aux`` DeepSeek-V2's
per sequence.  With ``kron_ffn`` each expert's three projections are
``kron(F1, F2)``, one factor set per expert, and the experts run as one
per-sample ``KronOp`` (``kron_linear_apply_batched``) over the ``(E, B*C,
D)`` buckets.

Each part is a ``telemetry.scope`` inside the layer's ``model.ffn``:
``model.moe.router`` (logits, top-k, ranks, aux), ``model.moe.dispatch``
(tokens into buckets), ``model.moe.experts``, ``model.moe.combine``
(weighted gather back) and ``model.moe.shared``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.layers import KronLinearSpec, kron_linear_apply_batched, kron_linear_init
from ..runtime import telemetry
from .common import act_fn, dense_init
from .config import ModelConfig, MoEConfig
from .ffn import ffn_apply, ffn_init


def _expert_specs(cfg: ModelConfig) -> tuple[KronLinearSpec, KronLinearSpec]:
    """Each expert's up (d_model -> d_expert) and down projection factors."""
    d, f = cfg.d_model, cfg.moe.d_expert
    return (KronLinearSpec.balanced(d, f, cfg.kron_factors),
            KronLinearSpec.balanced(f, d, cfg.kron_factors))


def moe_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    mc = cfg.moe
    d, f, e = cfg.d_model, mc.d_expert, mc.n_experts
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    p = {"router": dense_init(ks[0], d, e, jnp.float32)}
    if cfg.kron_ffn:
        up, down = _expert_specs(cfg)
        for name, k, spec in (("ew1", ks[1], up), ("ew3", ks[2], up), ("ew2", ks[3], down)):
            p[name] = jax.vmap(lambda kk, spec=spec: kron_linear_init(kk, spec, dtype))(
                jax.random.split(k, e))
    else:
        p["ew1"] = (jax.random.truncated_normal(ks[1], -2, 2, (e, d, f)) * std).astype(dtype)
        p["ew3"] = (jax.random.truncated_normal(ks[2], -2, 2, (e, d, f)) * std).astype(dtype)
        p["ew2"] = (jax.random.truncated_normal(ks[3], -2, 2, (e, f, d))
                    * (f ** -0.5)).astype(dtype)
    if mc.n_shared:
        p["shared"] = ffn_init(ks[4], cfg, dtype, d_ff=mc.n_shared * f)
    return p


def _capacity(s: int, mc: MoEConfig) -> int:
    c = int(s * mc.top_k * mc.capacity_factor / mc.n_experts) + 1
    return min(max(8, -(-c // 8) * 8), s * mc.top_k)  # mult of 8, <= all slots


def route(mc: MoEConfig, router_logits: jax.Array):
    """Top-k routing of (B, S, E) f32 logits: the softmax scores, each
    slot's expert and weight (B, S, k): the chosen scores, renormalised to
    sum 1 with ``norm_topk_prob``, else times ``routed_scaling_factor``."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, mc.top_k)
    if mc.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        top_p = top_p * mc.routed_scaling_factor
    return probs, top_i, top_p


def aux_loss(mc: MoEConfig, router_logits, probs, top_i) -> jax.Array:
    """The load-balance loss, unweighted.  ``seq_aux`` (DeepSeek-V2): per
    sequence ``sum_e f_e P_e`` with ``f_e`` expert e's share of the
    sequence's S*k choices over ``k/E`` and ``P_e`` its mean score, averaged
    over the batch.  Otherwise Switch's: ``E * sum_e`` (share of tokens
    whose first choice is e) (mean score of e) over the batch."""
    e = mc.n_experts
    if mc.seq_aux:
        s = top_i.shape[1]
        counts = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(1, 2))
        f = counts / (s * mc.top_k / e)  # (B, E)
        return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))
    top1 = jnp.argmax(router_logits, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(frac_tokens * frac_probs)


def _slots_one_seq(top_i, mc: MoEConfig, capacity: int):
    """One sequence's (S, k) expert choices -> the (E, C) map of the token in
    each bucket slot (-1: empty) and each choice's bucket slot and whether
    it was kept.  A choice ranks by token order within its expert; those
    ranked at or past ``capacity`` are dropped."""
    s, k = top_i.shape
    e = mc.n_experts
    e_flat = top_i.reshape(-1)  # (S*k,)
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
    # A dropped choice aims past the bucket's end, so that the scatter drops
    # it (aimed at slot (0, 0) it could overwrite the token kept there).
    src = jnp.full((e, capacity), -1, jnp.int32)
    src = src.at[e_flat, jnp.where(keep, rank, capacity)].set(
        t_flat.astype(jnp.int32), mode="drop"
    )
    return src, (slot_e, slot_c, keep)


def _experts(cfg: ModelConfig, p: dict, buckets: jax.Array, e_tp) -> jax.Array:
    """The routed experts on (B, E, C, D) buckets -> (B, E, C, D)."""
    from ..runtime.sharding import constrain

    act = act_fn(cfg.ffn_act)
    if cfg.kron_ffn:
        # one factor set per expert: the experts are the per-sample axis of
        # one batched KronOp over each expert's B*C rows
        b, e, c, d = buckets.shape
        xe = jnp.swapaxes(buckets, 0, 1).reshape(e, b * c, d)
        h = (act(kron_linear_apply_batched(p["ew1"], xe))
             * kron_linear_apply_batched(p["ew3"], xe))
        out = kron_linear_apply_batched(p["ew2"], h)
        return jnp.swapaxes(out.reshape(e, b, c, d), 0, 1)
    h = act(jnp.einsum("becd,edf->becf", buckets, p["ew1"])) * jnp.einsum(
        "becd,edf->becf", buckets, p["ew3"]
    )
    h = constrain(h, "batch", e_tp, None, "tp" if e_tp is None else None)
    return jnp.einsum("becf,efd->becd", h, p["ew2"])


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (B, S, D) -> (y, aux_loss)."""
    from ..runtime.sharding import _axes, _size, ambient_mesh, constrain

    mc = cfg.moe
    b, s, d = x.shape
    e = mc.n_experts
    capacity = _capacity(s, mc)

    # Routing is vmapped but touches only int32 index maps; ALL big-tensor
    # movement is batched take_along_axis gathers with pinned shardings —
    # XLA's scatter partitioner replicates large updates (measured f32
    # all-reduces of the whole (S*k, D) dispatch per layer, §Perf C3),
    # while gathers partition cleanly.
    with telemetry.scope("model.moe.router"):
        router_logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                                   p["router"].astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST)
        probs, top_i, top_p = route(mc, router_logits)
        src, (slot_e, slot_c, keep) = jax.vmap(
            lambda ti: _slots_one_seq(ti, mc, capacity))(top_i)  # src: (B, E, C)
        aux = aux_loss(mc, router_logits, probs, top_i)

    e_tp = None  # expert axis role: "tp" when expert-parallel applies
    mesh = ambient_mesh()
    if mesh is not None:
        _, tp_name = _axes(mesh)
        if mc.n_experts % _size(mesh, tp_name) == 0:
            e_tp = "tp"

    with telemetry.scope("model.moe.dispatch"):
        # (B, E*C, D) gather from token-major x
        valid = src >= 0
        buckets = jnp.take_along_axis(
            x, jnp.clip(src.reshape(b, e * capacity), 0)[..., None], axis=1
        ).reshape(b, e, capacity, d)
        buckets = jnp.where(valid[..., None], buckets, jnp.zeros((), x.dtype))
        buckets = constrain(buckets, "batch", e_tp, None, None)

    with telemetry.scope("model.moe.experts"):
        buckets_out = _experts(cfg, p, buckets, e_tp).astype(x.dtype)
        buckets_out = constrain(buckets_out, "batch", e_tp, None, None)

    with telemetry.scope("model.moe.combine"):
        # slot-major gather back + token-major reshape-sum (slots are
        # token-major by construction, so no scatter is ever needed)
        flat_idx = (slot_e * capacity + slot_c).astype(jnp.int32)  # (B, S*k)
        gathered = jnp.take_along_axis(
            buckets_out.reshape(b, e * capacity, d), flat_idx[..., None], axis=1
        )  # (B, S*k, D)
        gathered = constrain(gathered, "batch", None, None)
        w = jnp.where(keep, top_p.reshape(b, -1), 0.0)
        contrib = gathered * w[..., None].astype(x.dtype)
        y = contrib.reshape(b, s, mc.top_k, d).sum(axis=2)

    if mc.n_shared:
        with telemetry.scope("model.moe.shared"):
            y = y + ffn_apply(cfg, p["shared"], x)
    return y, aux


__all__ = ["moe_init", "moe_apply", "route", "aux_loss"]
