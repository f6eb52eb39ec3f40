"""GQA/MQA/MHA attention: RoPE, qk-norm, QKV-bias, sliding-window, KV cache.

The causal softmax core (``causal_attention``) has two executors.  On the
TPU, where positions are ``0..S-1`` (the caller says so, statically), no
sliding window applies, no multi-device mesh is installed and the sequence
tiles, it is the Pallas flash kernel (``kernels/flash_attention.py``): each
score tile stays in VMEM and blocks above the diagonal are skipped.
Everywhere else (the CPU, serving's cache positions, gemma-style windows,
context parallelism) it is the chunked XLA path: queries in chunks of
``q_chunk`` under ``lax.scan``, one f32 (B, H, q_chunk, S) block live at a
time and recomputed in the backward.  GQA keeps K/V un-repeated via a
grouped einsum, so TP sharding of q-heads never forces a KV all-gather.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import flash_attention
from ..runtime import telemetry
from ..runtime.sharding import ambient_mesh, constrain, tp_size
from .common import apply_rope, dense_init, rms_norm
from .config import ModelConfig

NEG_INF = -1e9


def _use_context_parallel(cfg: ModelConfig) -> bool:
    """Head-parallel TP needs n_heads % tp == 0; when it fails (qwen2.5:
    40 heads, qwen2-7b: 28, gemma: 8 on a 16-way model axis) XLA falls
    back to sharding head_dim — every score block then needs an f32 psum
    (measured 1.4 TB/device/step on qwen2.5-32b train).  Context
    parallelism instead shards the QUERY sequence over the model axis:
    scores are computed fully locally with replicated (small) K/V; the
    added comm is one K/V gather plus an S->feature reshard before the
    output projection.  Fleet measurement (EXPERIMENTS.md §Perf C1):
    -34..-79 % dominant term where q-heads don't divide; +8..+26 %
    REGRESSION when applied to archs where only KV heads don't divide
    (qwen3/llava/mixtral/jamba: q-head TP is fine and kv is cheap to
    split on head_dim) — hence the q-heads-only trigger."""
    tp = tp_size()
    return tp > 1 and cfg.n_heads % tp != 0


def attn_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,Hkv,hd), RoPE'd."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _grouped_scores(q: jax.Array, k: jax.Array, denom: float | None = None) -> jax.Array:
    """q (B,Sq,H,hd), k (B,Sk,Hkv,hd) -> (B,Hkv,G,Sq,Sk) in f32, divided by
    ``denom`` (default ``sqrt(hd)``)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        qg.astype(jnp.float32),
        k.astype(jnp.float32),
    ) / (math.sqrt(hd) if denom is None else denom)


def _grouped_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs (B,Hkv,G,Sq,Sk), v (B,Sk,Hkv,hd) -> (B,Sq,H,hd)."""
    b, hkv, g, sq, sk = probs.shape
    hd = v.shape[-1]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hkv * g, hd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_block(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                 arange_positions: bool) -> int | None:
    """The flash kernel's block where the core may run as the kernel, else
    None: on the TPU, for positions ``0..S-1``, no sliding window, no mesh
    of more than one device (a Pallas call is one device's program; the
    context-parallel path lives only on such a mesh), whole q-head groups
    and a sequence the kernel tiles (``flash_attention.block_for``)."""
    if not arange_positions or cfg.sliding_window or not _on_tpu():
        return None
    mesh = ambient_mesh()
    if (mesh is not None and mesh.size > 1) or q.shape[2] % k.shape[2]:
        return None
    return flash_attention.block_for(q.shape[1])


def causal_attention(
    cfg: ModelConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,
    *,
    q_chunk: int = 1024,
    denom: float | None = None,
    arange_positions: bool = False,
) -> jax.Array:
    """The causal softmax core: q (B,S,H,dqk), k (B,S,Hkv,dqk), v
    (B,S,Hkv,dv) -> (B,S,H,dv), scores divided by ``denom`` (default
    ``sqrt(dqk)``).  ``arange_positions`` states that ``positions`` are
    ``0..S-1``, which the flash kernel assumes (``_flash_block``); the
    chunked XLA path (queries in chunks of ``q_chunk``) runs otherwise.
    Each trace counts ``attn.core.kernel`` or ``attn.core.xla``."""
    with telemetry.scope("model.attn.core"):
        block = _flash_block(cfg, q, k, arange_positions)
        if block is not None:
            telemetry.counter_inc("attn.core.kernel")
            scale = 1.0 / (math.sqrt(q.shape[-1]) if denom is None else denom)
            return flash_attention.causal_flash_attention(q, k, v, scale=scale, block=block)
        telemetry.counter_inc("attn.core.xla")
        return _chunked_attention(cfg, q, k, v, positions, q_chunk=q_chunk, denom=denom)


def _chunked_attention(cfg, q, k, v, positions, *, q_chunk, denom):
    """The XLA executor of ``causal_attention``."""
    b, s = q.shape[:2]
    qb = min(q_chunk, s)
    if s % qb:
        qb = math.gcd(s, qb)
    nq = s // qb
    k_pos = positions  # (B, S) or (S,)
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos, (b, s))

    ctx_parallel = _use_context_parallel(cfg)
    if ctx_parallel:
        # context parallelism: queries S-sharded over the model axis, K/V
        # replicated (Hkv*hd is small) — scores stay fully local
        q = constrain(q, "batch", "tp", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)

    qc = q.reshape(b, nq, qb, *q.shape[2:])
    pc = k_pos.reshape(b, nq, qb)

    def chunk_attn(qi, qpos):
        """One q-chunk: (B, qb, H, hd), (B, qb) -> (B, qb, H, hd)."""
        if ctx_parallel:
            qi = constrain(qi, "batch", "tp", None, None)
        scores = _grouped_scores(qi, k, denom)  # (B,Hkv,G,qb,S)
        causal = k_pos[:, None, None, None, :] <= qpos[:, None, None, :, None]
        if cfg.sliding_window:
            causal &= (
                k_pos[:, None, None, None, :]
                > qpos[:, None, None, :, None] - cfg.sliding_window
            )
        scores = jnp.where(causal, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        # cast INSIDE the chunk: the stacked scan output (and any reshard
        # XLA inserts before the out-projection) must ride bf16, not the
        # f32 accumulator type (measured 2x collective bytes otherwise)
        out = _grouped_out(probs, v).astype(qi.dtype)
        if ctx_parallel:
            out = constrain(out, "batch", "tp", None, None)
        return out

    # flash-attention-style backward: recompute each chunk's (qb, S) score
    # block instead of saving it — otherwise nq f32 blocks survive per layer
    chunk_attn = jax.checkpoint(chunk_attn)

    def body(_, args):
        return None, chunk_attn(*args)

    _, outs = jax.lax.scan(
        body, None, (jnp.swapaxes(qc, 0, 1), jnp.swapaxes(pc, 0, 1))
    )  # (nq, B, qb, H, hd)
    return jnp.swapaxes(outs, 0, 1).reshape(b, s, q.shape[2], v.shape[-1])


def attn_forward(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    q_chunk: int = 1024,
    return_kv: bool = False,
    arange_positions: bool = False,
):
    """Causal full-sequence attention (train / prefill); ``arange_positions``
    as ``causal_attention``'s."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = causal_attention(cfg, q, k, v, positions, q_chunk=q_chunk,
                           arange_positions=arange_positions)
    y = (out.reshape(b, s, -1).astype(x.dtype)) @ p["wo"]
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array        # (B, L, Hkv, hd)  model dtype, or int8 when quantized
    v: jax.Array
    pos: jax.Array      # (L,) absolute position of each slot, -1 = empty


class QuantKVCache(NamedTuple):
    """int8 KV cache (cfg.kv_quant): per-(token, head) absmax scales.

    Halves the dominant serving buffer (the paper-style memory-movement
    lever applied to decode: the cache is read in full every token, so
    bytes == time).  Standard int8-KV accuracy envelope (~2^-7 relative)."""

    k: jax.Array        # (B, L, Hkv, hd) int8
    v: jax.Array        # int8
    k_scale: jax.Array  # (B, L, Hkv, 1) f32
    v_scale: jax.Array
    pos: jax.Array


def _quantize_kv(t: jax.Array):
    """(..., hd) -> int8 values + f32 absmax scale over hd."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window:
        return min(max_len, cfg.sliding_window)
    return max_len


def attn_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    l = cache_len(cfg, max_len)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    if cfg.kv_quant:
        return QuantKVCache(
            k=jnp.zeros((batch, l, hkv, hd), jnp.int8),
            v=jnp.zeros((batch, l, hkv, hd), jnp.int8),
            k_scale=jnp.zeros((batch, l, hkv, 1), jnp.float32),
            v_scale=jnp.zeros((batch, l, hkv, 1), jnp.float32),
            pos=jnp.full((l,), -1, jnp.int32),
        )
    return KVCache(
        k=jnp.zeros((batch, l, hkv, hd), dtype),
        v=jnp.zeros((batch, l, hkv, hd), dtype),
        pos=jnp.full((l,), -1, jnp.int32),
    )


def attn_decode(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,          # (B, 1, D)
    cache: KVCache,
    pos: jax.Array,        # int32 scalar, or (B,) for per-slot positions
) -> tuple[jax.Array, KVCache]:
    """One incremental token against the KV cache.

    Two position modes.  Scalar ``pos`` (the one-shot path): every row is
    at the same position and ``cache.pos`` is shared, shape (L,).  Vector
    ``pos`` of shape (B,) (the continuous-batching path, docs/serving.md):
    each decode slot runs its OWN clock — requests admitted mid-flight sit
    at different positions — and ``cache.pos`` must be per-row, (B, L)
    (see ``model.cache_to_slots``).  Positions are request-relative in
    that mode, so RoPE numerics match a batch-of-one run exactly.
    """
    b = x.shape[0]
    l = cache.k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    positions = pos[:, None] if per_slot else jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    slot = (pos % l).astype(jnp.int32)  # ring buffer (== pos w/o SWA)
    zero = jnp.int32(0)
    quant = isinstance(cache, QuantKVCache)

    if per_slot:
        rows = jnp.arange(b)

        def scatter(buf, new):
            # row i writes its own slot: (B, L, ...)[i, slot[i]] = new[i, 0]
            return buf.at[rows, slot].set(new[:, 0].astype(buf.dtype))

    else:

        def scatter(buf, new):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (zero, slot) + (zero,) * (buf.ndim - 2)
            )

    if quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        kk = scatter(cache.k, kq)
        vv = scatter(cache.v, vq)
        kss = scatter(cache.k_scale, ks)
        vss = scatter(cache.v_scale, vs)
        k = _dequantize_kv(kk, kss, x.dtype)
        v = _dequantize_kv(vv, vss, x.dtype)
    else:
        kk = vv = kss = vss = None
        k = scatter(cache.k, k_new)
        v = scatter(cache.v, v_new)
    if per_slot:
        cpos = cache.pos.at[rows, slot].set(pos)        # (B, L)
        valid = (cpos >= 0) & (cpos <= pos[:, None])
        if cfg.sliding_window:
            valid &= cpos > pos[:, None] - cfg.sliding_window
        vmask = valid[:, None, None, None, :]
    else:
        cpos = jax.lax.dynamic_update_slice(
            cache.pos, jnp.full((1,), pos, jnp.int32), (slot,)
        )
        valid = (cpos >= 0) & (cpos <= pos)
        if cfg.sliding_window:
            valid &= cpos > pos - cfg.sliding_window
        vmask = valid[None, None, None, None, :]
    scores = _grouped_scores(q, k)  # (B,Hkv,G,1,L)
    scores = jnp.where(vmask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(probs, v).astype(x.dtype)  # (B,1,H,hd)
    y = out.reshape(b, 1, -1) @ p["wo"]
    if quant:
        return y, QuantKVCache(kk, vv, kss, vss, cpos)
    return y, KVCache(k, v, cpos)


def attn_prefill_cache(
    cfg: ModelConfig,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,
    max_len: int,
):
    """Build a cache from full-sequence K/V (used by prefill)."""
    b, s = k.shape[:2]
    l = cache_len(cfg, max_len)
    if s >= l:
        # keep the last l entries (ring layout: slot = pos % l)
        kk, vv = k[:, s - l :], v[:, s - l :]
        pp = positions[s - l :] if positions.ndim == 1 else positions[0, s - l :]
        # ring order
        slots = pp % l
        order = jnp.argsort(slots)
        kk, vv, pp = kk[:, order], vv[:, order], pp[order].astype(jnp.int32)
    else:
        pad = l - s
        kk = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = positions if positions.ndim == 1 else positions[0]
        pp = jnp.pad(pp.astype(jnp.int32), (0, pad), constant_values=-1)
    if cfg.kv_quant:
        kq, ks = _quantize_kv(kk)
        vq, vs = _quantize_kv(vv)
        return QuantKVCache(kq, vq, ks, vs, pp)
    return KVCache(kk, vv, pp)


__all__ = [
    "attn_init",
    "attn_forward",
    "causal_attention",
    "attn_decode",
    "attn_cache_init",
    "attn_prefill_cache",
    "KVCache",
    "QuantKVCache",
    "cache_len",
]
