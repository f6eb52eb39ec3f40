"""Model assembly: embedding -> (prelude + scanned periodic stack) -> head.

The layer stack is scanned over the config's repeating *period* (Jamba:
9 scan steps of an 8-layer period; dense models: n_layers steps of 1), with
``jax.checkpoint`` around the scan body (full remat: only period boundaries
live during backward).  Irregular prefixes (DeepSeek's dense first layer)
are applied unscanned as the "prelude".

Three entry points:
  forward(cfg, params, tokens, embeds=None)        -> logits (train)
  prefill(cfg, params, tokens, max_len, ...)       -> (logits, cache)
  decode_step(cfg, params, cache, tokens, pos)     -> (logits, cache)

An attention layer is GQA (``attention.py``), or multi-head latent
attention (``mla.py``) where ``kv_lora_rank > 0``; MLA has no decode cache,
so ``prefill``, ``decode_step`` and ``init_cache`` refuse it.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import ffn as ffn_mod
from . import mla
from . import moe as moe_mod
from . import ssm
from ..runtime import telemetry
from ..runtime.sharding import constrain
from .common import embed_init, rms_norm
from .config import LayerSpec, ModelConfig


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(key: jax.Array, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict[str, Any] = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if spec.kind == "attn" and cfg.mla:
        p["mixer"] = mla.mla_init(k1, cfg, dtype)
    elif spec.kind == "attn":
        p["mixer"] = attn.attn_init(k1, cfg, dtype)
    else:
        p["mixer"] = ssm.mamba_init(k1, cfg, dtype)
    if spec.moe:
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn"] = moe_mod.moe_init(k2, cfg, dtype)
    elif cfg.d_ff:
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn"] = ffn_mod.ffn_init(k3, cfg, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    plan = cfg.layer_plan()
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, cfg.padded_vocab, cfg.d_model, dtype).T
    # prelude
    pre = cfg.prelude_len
    params["prelude"] = [
        _layer_init(jax.random.fold_in(k_layers, 1000 + i), cfg, plan[i], dtype)
        for i in range(pre)
    ]
    # periodic stack: one stacked entry per position in the period
    period, n_periods = cfg.period, cfg.n_periods
    stack = {}
    for pos in range(period):
        spec = plan[pre + pos]
        ks = jax.random.split(jax.random.fold_in(k_layers, pos), n_periods)
        stack[f"pos{pos}"] = jax.vmap(
            lambda kk: _layer_init(kk, cfg, spec, dtype)
        )(ks)
    params["stack"] = stack
    return params


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _layer_forward(cfg, spec: LayerSpec, p, x, positions):
    """Full-sequence layer over ``positions``, which are ``0..S-1`` (the
    attention core may assume it).  Returns (x, aux,
    kv_for_cache|state|None).  Each half (norm, mixer or FFN, residual add)
    is a ``model.*`` scope."""
    with telemetry.scope("model.attn" if spec.kind == "attn" else "model.ssm"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if spec.kind == "attn" and cfg.mla:
            mix = mla.mla_forward(cfg, p["mixer"], h, positions, arange_positions=True)
            cache_out = None
        elif spec.kind == "attn":
            mix, cache_out = attn.attn_forward(cfg, p["mixer"], h, positions,
                                               return_kv=True, arange_positions=True)
        else:
            mix, cache_out = ssm.mamba_forward(cfg, p["mixer"], h, return_state=True)
        x = x + mix
    aux = jnp.zeros((), jnp.float32)
    with telemetry.scope("model.ffn"):
        if spec.moe:
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            y, aux = moe_mod.moe_apply(cfg, p["ffn"], h2)
            x = x + y
        elif cfg.d_ff:
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + ffn_mod.ffn_apply(cfg, p["ffn"], h2)
    return x, aux, cache_out


def _layer_decode(cfg, spec: LayerSpec, p, x, cache, pos):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn":
        mix, cache = attn.attn_decode(cfg, p["mixer"], h, cache, pos)
    else:
        mix, cache = ssm.mamba_decode(cfg, p["mixer"], h, cache)
    x = x + mix
    if spec.moe:
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _ = moe_mod.moe_apply(cfg, p["ffn"], h2)
        x = x + y
    elif cfg.d_ff:
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + ffn_mod.ffn_apply(cfg, p["ffn"], h2)
    return x, cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens, embeds):
    x = params["embed"][tokens]  # (B, S, D) gather
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.d_model)).astype(x.dtype)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    return constrain(x, "batch", None, None)


def _head(cfg, params, x):
    with telemetry.scope("model.head"):
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = h @ params["embed"].T
        else:
            logits = h @ params["lm_head"]
        # mask padded vocab rows so they never win the softmax
        if cfg.padded_vocab != cfg.vocab:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
            logits = jnp.where(pad_mask, -1e9, logits.astype(jnp.float32))
        return constrain(logits.astype(jnp.float32), "batch", None, "tp")


# ---------------------------------------------------------------------------
# Forward (train) / prefill / decode
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    embeds: jax.Array | None = None,
):
    """Teacher-forced forward.  Returns (logits, aux_loss)."""
    x = _embed(cfg, params, tokens, embeds)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    plan = cfg.layer_plan()
    aux_total = jnp.zeros((), jnp.float32)

    for i, p_l in enumerate(params["prelude"]):
        x, aux, _ = _layer_forward(cfg, plan[i], p_l, x, positions)
        aux_total = aux_total + aux

    pre, period = cfg.prelude_len, cfg.period
    specs = tuple(plan[pre : pre + period])

    def one_layer(spec, p_l, x):
        y, aux, _ = _layer_forward(cfg, spec, p_l, x, positions)
        return y, aux

    if cfg.remat:
        # nested remat: the scan body is checkpointed (only period
        # boundaries survive the forward) AND each layer inside is
        # checkpointed (the period backward re-materializes one layer at a
        # time instead of all `period` layers at once — 8x live-memory cut
        # for Jamba's 8-layer period).
        one_layer = jax.checkpoint(one_layer, static_argnums=(0,))

    def body(carry, p_period):
        x, aux_acc = carry
        # Scan-carry boundaries are the remat-saved activations (one per
        # period, ALL live through the backward pass).  Pinning them
        # ("batch", None, "tp") stores each boundary d_model-sharded over
        # the model axis — Megatron-sequence-parallel-style — cutting the
        # dominant training buffer TP-fold (observed 16x: 10.7 GB -> 0.7 GB
        # per device on qwen2.5-32b).  The all-gather to recompute is one
        # (B_mb, S, D) gather per period per direction, already part of the
        # collective roofline term.
        x = constrain(x, "batch", None, "tp")
        for pos in range(period):
            x, aux = one_layer(specs[pos], p_period[f"pos{pos}"], x)
            aux_acc = aux_acc + aux
        return (constrain(x, "batch", None, "tp"), aux_acc), None

    if cfg.remat:
        body = jax.checkpoint(body)
    # the scan's own work (each layer's weights sliced from the stack, its
    # gradients stacked back) is the stack's; the layers name their halves
    with telemetry.scope("model.stack"):
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["stack"])
    return _head(cfg, params, x), aux_total


def _refuse_mla_serving(cfg: ModelConfig, entry: str) -> None:
    """Serving has no latent KV cache: an MLA configuration is refused
    rather than given a GQA cache it does not have."""
    if cfg.mla:
        raise NotImplementedError(
            f"{entry}: {cfg.name} uses multi-head latent attention "
            f"(kv_lora_rank={cfg.kv_lora_rank}), which has no KV cache or decode "
            "path here; only training (forward) runs it")


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    _refuse_mla_serving(cfg, "init_cache")
    dtype = jnp.dtype(cfg.dtype)
    plan = cfg.layer_plan()

    def one(spec: LayerSpec):
        if spec.kind == "attn":
            return attn.attn_cache_init(cfg, batch, max_len, dtype)
        return ssm.mamba_cache_init(cfg, batch, dtype)

    pre, period, n_periods = cfg.prelude_len, cfg.period, cfg.n_periods
    prelude = [one(plan[i]) for i in range(pre)]
    stack = {
        f"pos{pos}": jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n_periods,) + l.shape),
            one(plan[pre + pos]),
        )
        for pos in range(period)
    }
    return {"prelude": prelude, "stack": stack}


# ---------------------------------------------------------------------------
# Slot-form caches (continuous batching, docs/serving.md)
#
# ``prefill``/``init_cache`` build caches whose attention ``pos`` leaf is
# SHARED across the batch, shape (L,) — every row at the same position.
# Continuous batching mixes requests at different positions in one decode
# batch, so the serving engine converts to "slot form": pos per-row, (B, L),
# after which EVERY cache leaf carries the batch on one uniform axis
# (prelude: axis 0; scanned stack: axis 1, behind the n_periods axis) and
# whole requests can be moved between caches with a gather + scatter.
# ---------------------------------------------------------------------------


def _is_cache(x) -> bool:
    return isinstance(x, (attn.KVCache, attn.QuantKVCache))


def cache_to_slots(cache: dict, true_lens: jax.Array | None = None) -> dict:
    """Broadcast shared attention ``pos`` leaves to per-row (B, L).

    ``true_lens`` (B,) marks each row's real prompt length: a bucketed
    prefill pads every prompt to the bucket, and the pad tokens' K/V land
    in cache entries with position >= true_len — those entries are masked
    to pos = -1 (empty) so later decode steps never attend to pad keys.
    """

    def one(c, stacked: bool):
        if not _is_cache(c):
            return c  # MambaCache: batch-leading already, nothing shared
        pos = c.pos
        if stacked:  # (n_periods, L) -> (n_periods, B, L)
            b, l = c.k.shape[1], c.k.shape[2]
            if pos.ndim == 2:
                pos = jnp.broadcast_to(pos[:, None, :], (pos.shape[0], b, l))
        else:  # (L,) -> (B, L)
            b, l = c.k.shape[0], c.k.shape[1]
            if pos.ndim == 1:
                pos = jnp.broadcast_to(pos[None, :], (b, l))
        if true_lens is not None:
            tl = jnp.asarray(true_lens, jnp.int32)  # (B,)
            keep = pos < (tl[None, :, None] if stacked else tl[:, None])
            pos = jnp.where(keep, pos, -1)
        return c._replace(pos=pos.astype(jnp.int32))

    return {
        "prelude": [one(c, False) for c in cache["prelude"]],
        "stack": {
            k: jax.tree.map(lambda c: one(c, True), v, is_leaf=_is_cache)
            for k, v in cache["stack"].items()
        },
    }


def cache_take(cache: dict, row) -> dict:
    """Extract one request's cache rows as a batch-1 slot-form cache.
    Requires slot form (``cache_to_slots``); ``row`` may be traced."""
    row = jnp.asarray(row, jnp.int32)
    return {
        "prelude": jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=0),
            cache["prelude"],
        ),
        "stack": jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=1),
            cache["stack"],
        ),
    }


def cache_put(dst: dict, src: dict, slot) -> dict:
    """Write a batch-1 slot-form cache (``cache_take`` of a prefill) into
    decode slot ``slot`` of ``dst`` — the admission primitive of the
    continuous-batching engine.  Cache lengths L must match (both sides
    built with the same ``max_len``)."""
    slot = jnp.asarray(slot, jnp.int32)
    return {
        "prelude": jax.tree.map(
            lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, axis=0
            ),
            dst["prelude"], src["prelude"],
        ),
        "stack": jax.tree.map(
            lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, axis=1
            ),
            dst["stack"], src["stack"],
        ),
    }


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    max_len: int,
    embeds: jax.Array | None = None,
):
    """Full-sequence pass that also builds the decode cache."""
    _refuse_mla_serving(cfg, "prefill")
    x = _embed(cfg, params, tokens, embeds)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    plan = cfg.layer_plan()

    def to_cache(spec: LayerSpec, raw):
        if spec.kind == "attn":
            k, v = raw
            return attn.attn_prefill_cache(cfg, k, v, positions, max_len)
        conv_tail, h = raw
        return ssm.MambaCache(conv=conv_tail, h=h)

    prelude_cache = []
    for i, p_l in enumerate(params["prelude"]):
        x, _, raw = _layer_forward(cfg, plan[i], p_l, x, positions)
        prelude_cache.append(to_cache(plan[i], raw))

    pre, period = cfg.prelude_len, cfg.period
    specs = tuple(plan[pre : pre + period])

    def body(x, p_period):
        caches = {}
        for pos in range(period):
            x, _, raw = _layer_forward(
                cfg, specs[pos], p_period[f"pos{pos}"], x, positions
            )
            caches[f"pos{pos}"] = to_cache(specs[pos], raw)
        return x, caches

    x, stack_cache = jax.lax.scan(body, x, params["stack"])
    return _head(cfg, params, x), {"prelude": prelude_cache, "stack": stack_cache}


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,   # (B, 1)
    pos: jax.Array,      # scalar int32, or (B,) per-slot (slot-form cache)
):
    """One incremental token.  Returns (logits (B,1,V), new_cache).

    Scalar ``pos``: all rows at the same position (one-shot serving).
    Vector ``pos`` (B,): each decode slot on its own clock — requires the
    cache in slot form (``cache_to_slots``); see ``attn.attn_decode``."""
    _refuse_mla_serving(cfg, "decode_step")
    x = _embed(cfg, params, tokens, None)
    plan = cfg.layer_plan()

    new_prelude = []
    for i, (p_l, c_l) in enumerate(zip(params["prelude"], cache["prelude"])):
        x, c_l = _layer_decode(cfg, plan[i], p_l, x, c_l, pos)
        new_prelude.append(c_l)

    pre, period = cfg.prelude_len, cfg.period
    specs = tuple(plan[pre : pre + period])

    def body(x, xs):
        p_period, c_period = xs
        new_c = {}
        for pos_i in range(period):
            x, c = _layer_decode(
                cfg, specs[pos_i], p_period[f"pos{pos_i}"], x,
                c_period[f"pos{pos_i}"], pos,
            )
            new_c[f"pos{pos_i}"] = c
        return x, new_c

    x, new_stack = jax.lax.scan(body, x, (params["stack"], cache["stack"]))
    return _head(cfg, params, x), {"prelude": new_prelude, "stack": new_stack}


__all__ = [
    "init_params",
    "forward",
    "prefill",
    "decode_step",
    "init_cache",
    "cache_to_slots",
    "cache_take",
    "cache_put",
]
