"""Plain reference of a DeepSeek-V2-shaped decoder (multi-head latent
attention with YaRN RoPE; a dense first layer, then layers of routed and
shared experts) whose feed-forward projections are Kronecker products, and
of its AdamW training steps.

Everything is float32 at the precision ``mode`` names (``numerics``); each
``kron_ffn`` projection is the dense ``kron(F_1, F_2)`` matrix, and the
routed experts run one after the other, each on the tokens it keeps.  The
parameters are stored as the configuration states (``dtype``): after each
update they are rounded to it.

The equations, from the configuration file's keys (``modeling_deepseek.py``
written out):

* latent attention: ``q = x Wq`` split per head into ``[q_nope | q_pe]``;
  ``[c | k_pe] = x Wkv_a``; ``[k_nope | v] = rms(c) Wkv_b`` per head; RoPE
  with YaRN's frequencies on ``q_pe`` and the one ``k_pe`` all heads share,
  cos and sin times ``mscale(mscale) / mscale(mscale_all_dim)`` (rotate-half;
  the source first de-interleaves the rotary halves, a fixed permutation
  of weight columns on random weights); scores scaled by ``(dn + dr)^-0.5 *
  mscale(mscale_all_dim)^2``; causal softmax; ``Wo``.  Queries in blocks.
* routing: softmax of the float32 logits ``x Wg``, the top ``k`` scores,
  renormalised if ``norm_topk_prob`` else times ``routed_scaling_factor``;
  per sequence, an expert keeps the first ``C`` tokens that chose it, in
  token order (``C`` as the program sizes its buckets: the multiple of 8 at
  or above ``floor(S k cf / E) + 1``, at most ``S k``).
* the balance loss per sequence, ``sum_e f_e P_e`` with ``f_e`` the
  expert's count of the ``S k`` choices over ``S k / E`` and ``P_e`` its
  mean score, averaged over the batch, summed over the layers and weighted
  by ``aux_loss_alpha``.

The parameters arrive in the layout the benchmark made them in (the
program's, which is data here): ``embed``, ``lm_head``, ``final_norm``,
the dense first layers as a list under ``prelude`` and the expert layers
stacked on a leading axis under ``stack/pos0``.  A norm's stored value is
an offset from 1: the weight is ``1 + scale``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .lm import CE_CHUNK, kron_dense, lr_at, rms
from .numerics import einsum

NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
Q_BLOCK = 512  # queries per block of attention scores


def yarn_range(cfg: dict) -> tuple[int, int]:
    """The first and last rotary pair of YaRN's ramp."""
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def turns(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))

    return (max(math.floor(turns(y["beta_fast"])), 0),
            min(math.ceil(turns(y["beta_slow"])), dim - 1))


def inv_freq(cfg: dict):
    """YaRN's inverse frequencies of the rotary pairs."""
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    low, high = yarn_range(cfg)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y["factor"] * ramp + extra * (1.0 - ramp)


def mscale(cfg: dict, key: str) -> float:
    y = cfg["rope_scaling"]
    return 0.1 * y[key] * math.log(y["factor"]) + 1.0 if y["factor"] > 1 else 1.0


def softmax_scale(cfg: dict) -> float:
    m = mscale(cfg, "mscale_all_dim")
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, freqs, scale=1.0):
    """Rotate-half RoPE on (B, S, H, d) at positions 0..S-1, cos and sin
    times ``scale``."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :] * scale, jnp.sin(ang)[:, None, :] * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg, p, x, mode):
    b, s, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    freqs = inv_freq(cfg)
    cos_scale = mscale(cfg, "mscale") / mscale(cfg, "mscale_all_dim")
    q = einsum("bsd,de->bse", x, p["wq"], mode).reshape(b, s, h, dn + dr)
    ckv = einsum("bsd,de->bse", x, p["wkv_a"], mode)
    c = rms(ckv[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = einsum("bsr,re->bse", c, p["wkv_b"], mode).reshape(b, s, h, dn + dv)
    k_pe = rope(ckv[..., None, r:], freqs, cos_scale)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], freqs, cos_scale)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    v = kv[..., dn:]
    scale = softmax_scale(cfg)
    block = math.gcd(s, Q_BLOCK)

    @jax.checkpoint
    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = einsum("bqhd,bkhd->bhqk", qb, k, mode) * scale
        rows = i * block + jnp.arange(block)
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return einsum("bhqk,bkhd->bqhd", probs, v, mode)

    out = jax.lax.map(one_block, jnp.arange(s // block))  # (n, B, block, H, dv)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * dv)
    return einsum("bse,ed->bsd", out, p["wo"], mode)


def ffn(p, x, mode):
    w1, w3, w2 = (kron_dense(p[k]["factors"]) for k in ("w1", "w3", "w2"))
    h = (jax.nn.silu(einsum("...d,df->...f", x, w1, mode))
         * einsum("...d,df->...f", x, w3, mode))
    return einsum("...f,fd->...d", h, w2, mode)


def capacity(cfg: dict, s: int) -> int:
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    c = math.floor(s * k * cfg["capacity_factor"] / e) + 1
    return min(max(8, -(-c // 8) * 8), s * k)


def route(cfg, x, router, mode):
    """Scores (B, S, E), chosen experts and their weights (B, S, k)."""
    probs = jax.nn.softmax(einsum("bsd,de->bse", x, router, mode), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        top_p = top_p * cfg["routed_scaling_factor"]
    return probs, top_i, top_p


def balance_loss(cfg, probs, top_i):
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s = probs.shape[1]
    chosen = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=2)  # (B, S, E)
    f = jnp.sum(chosen, axis=1) / (s * k / e)
    return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


def routed_experts(cfg, p, x, top_i, top_p, mode):
    """Each expert on the first ``C`` tokens of each sequence that chose it,
    in token order, weighted by its score: (B, S, D)."""
    b, s, d = x.shape
    e, c = cfg["n_routed_experts"], capacity(cfg, s)
    chosen = jax.nn.one_hot(top_i, e, dtype=jnp.float32)  # (B, S, k, E)
    gate = jnp.sum(chosen * top_p[..., None], axis=2)  # the weight, 0 if not chosen
    picked = jnp.sum(chosen, axis=2) > 0  # (B, S, E)
    # per sequence and expert: the tokens that chose it first, in token
    # order, then the others (weight 0 in the sum below)
    key = jnp.where(picked, 0, s) + jnp.arange(s)[None, :, None]
    rows = jnp.argsort(key, axis=1)[:, :c, :]  # (B, C, E)

    @jax.checkpoint
    def one_expert(args):
        ex, f1, f3, f2 = args
        xe = jnp.take_along_axis(x, rows[..., ex][..., None], axis=1)  # (B, C, D)
        ge = jnp.take_along_axis(gate[..., ex], rows[..., ex], axis=1)  # (B, C)
        ye = ffn({"w1": {"factors": f1}, "w3": {"factors": f3}, "w2": {"factors": f2}},
                 xe, mode)
        return ye * ge[..., None]

    outs = jax.lax.map(one_expert, (jnp.arange(e), p["ew1"]["factors"],
                                    p["ew3"]["factors"], p["ew2"]["factors"]))
    # each expert's rows added back where they came from
    return jnp.zeros_like(x).at[jnp.arange(b)[:, None, None], rows].add(
        jnp.moveaxis(outs, 0, 2))


def moe(cfg, p, x, mode):
    """The expert layer: (y, its balance loss)."""
    probs, top_i, top_p = route(cfg, x, p["router"], mode)
    y = routed_experts(cfg, p, x, top_i, top_p, mode) + ffn(p["shared"], x, mode)
    return y, balance_loss(cfg, probs, top_i)


def layer(cfg, lp, x, mode, dense: bool):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, lp["mixer"], rms(x, lp["ln1"], eps), mode)
    h = rms(x, lp["ln2"], eps)
    if dense:
        return x + ffn(lp["ffn"], h, mode), jnp.zeros((), jnp.float32)
    y, aux = moe(cfg, lp["ffn"], h, mode)
    return x + y, aux


def loss(cfg, params, tokens, labels, mode="highest"):
    """Mean next-token cross-entropy over every position, plus the balance
    loss of every expert layer times ``aux_loss_alpha``."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)  # no-op when f32
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for lp in params["prelude"]:
        x, _ = jax.checkpoint(lambda lp, x: layer(cfg, lp, x, mode, True))(lp, x)

    stack = params["stack"]["pos0"]

    @jax.checkpoint
    def body(x, i):  # the layer's weights are sliced here, not saved per layer
        return layer(cfg, jax.tree.map(lambda a: a[i], stack), x, mode, False)

    x, auxes = jax.lax.scan(body, x, jnp.arange(jax.tree.leaves(stack)[0].shape[0]))
    aux = aux + jnp.sum(auxes)
    h = rms(x, params["final_norm"], eps).reshape(-1, x.shape[-1])
    labels = labels.reshape(-1)

    @jax.checkpoint
    def block_nll(hb, yb):
        logits = einsum("td,dv->tv", hb, params["lm_head"], mode)[:, : cfg["vocab_size"]]
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0])

    n = h.shape[0]
    chunk = math.gcd(n, CE_CHUNK)
    nll = jax.lax.map(lambda a: block_nll(*a), (h.reshape(n // chunk, chunk, -1),
                                                labels.reshape(n // chunk, chunk)))
    return jnp.sum(nll) / n + cfg["aux_loss_alpha"] * aux


def _is_norm(path) -> bool:
    return any(getattr(k, "key", None) in NORMS for k in path)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _grads(params, tokens, labels, *, cfg_items, mode):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens, labels, mode))(params)


@functools.partial(jax.jit, static_argnames=("opt_items", "store"),
                   donate_argnums=(0, 1, 2))
def _update(params, m, v, grads, step, *, opt_items, store):
    """One AdamW update: global-norm clipping, bias-corrected moments,
    decoupled weight decay on weight matrices (not on norm scales).  The
    parameters are held in float32 and rounded to ``store``'s precision.  (A jit of its
    own: fused with the gradient's, XLA held several copies of the
    gradients and moments at once.)"""
    opt = dict(opt_items)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12)),
        grads)
    t = step + 1
    lr = lr_at(opt, t)
    b1, b2 = opt["b1"], opt["b2"]
    bc1 = 1 - b1 ** t.astype(jnp.float32)
    bc2 = 1 - b2 ** t.astype(jnp.float32)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def update(path, p, mm, vv):
        u = (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
        if not _is_norm(path):
            u = u + opt["weight_decay"] * p
        # reduce_precision, not a round trip through ``store``: XLA may drop
        # an f32 -> bf16 -> f32 round trip (excess precision), and on a TPU
        # that kept every update below half a bf16 step, which a stored
        # bf16 parameter loses
        f = jnp.finfo(jnp.dtype(store))
        return jax.lax.reduce_precision(p - lr * u, exponent_bits=f.nexp,
                                        mantissa_bits=f.nmant)

    params = jax.tree_util.tree_map_with_path(update, params, m, v)
    g_norms = jax.tree.map(lambda g: jnp.linalg.norm(g.ravel()), grads)
    return params, m, v, g_norms


def train_steps(cfg: dict, opt: dict, params, batches, mode: str = "highest",
                dtype=jnp.bfloat16) -> dict:
    """Run ``len(batches)`` reference steps from ``params``, which it
    consumes: each array is deleted once read, so that a chip holds the
    float32 copy and not both.

    Returns the loss of every step, every leaf's gradient norm at the first
    step (clipped, as the optimizer takes it) and every leaf's change over
    all the steps, each keyed by the leaf's path."""
    cfg_items = tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg.items() if not isinstance(v, list) and k != "published"))
    opt_items = tuple(sorted(opt.items()))
    store = jnp.dtype(dtype).name

    def take(a):
        stored = jnp.asarray(a, dtype)
        host = np.asarray(stored)
        f32 = jnp.array(stored, jnp.float32, copy=True).block_until_ready()
        for x in {id(a): a, id(stored): stored}.values():
            if isinstance(x, jax.Array):
                x.delete()
        return host, f32

    leaves, tree = jax.tree.flatten(params)
    start, params = (tree.unflatten(x) for x in zip(*map(take, leaves)))
    del leaves
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches):
        val, grads = _grads(params, tokens, labels, cfg_items=cfg_items, mode=mode)
        params, m, v, g_norms = _update(params, m, v, grads, jnp.int32(i),
                                        opt_items=opt_items, store=store)
        losses.append(float(val))
        if first is None:
            first = g_norms
    del m, v
    change = jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - jnp.asarray(b).astype(jnp.float32)).ravel()),
        params, start)
    flat = lambda t: {jax.tree_util.keystr(p): float(x)  # noqa: E731
                      for p, x in jax.tree_util.tree_leaves_with_path(t)}
    return {"loss": losses, "grad_norm": flat(first), "change_norm": flat(change)}
