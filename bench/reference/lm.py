"""Plain reference of a dense GQA decoder (Qwen3: qk-norm, RoPE, SwiGLU)
whose feed-forward projections are Kronecker products, and of its AdamW
training steps.

Everything is float32 at the precision ``mode`` names (``numerics``); each
``kron_ffn`` projection is the dense ``kron(F_1, F_2)`` matrix.  The
parameters are stored as the configuration states (``dtype``): after each
update they are rounded to it, as a bf16 checkpoint would hold them.

The parameters arrive in the layout the benchmark made them in (the
program's, which is data here): ``embed``, ``final_norm``, optionally
``lm_head``, and the layers stacked on a leading axis under
``stack/pos0``.  A norm's stored value is an offset from 1: the weight is
``1 + scale``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .numerics import einsum

NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")
CE_CHUNK = 512  # tokens per block of logits in the loss


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, theta):
    """Rotate-half RoPE on (B, S, H, hd) at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg, p, x, mode):
    b, s, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = einsum("bsd,de->bse", x, p["wq"], mode).reshape(b, s, h, hd)
    k = einsum("bsd,de->bse", x, p["wk"], mode).reshape(b, s, hkv, hd)
    v = einsum("bsd,de->bse", x, p["wv"], mode).reshape(b, s, hkv, hd)
    if cfg.get("qk_norm"):
        q, k = rms(q, p["q_norm"], eps), rms(k, p["k_norm"], eps)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = einsum("bhqk,bkhd->bqhd", probs, v, mode).reshape(b, s, h * hd)
    return einsum("bse,ed->bsd", out, p["wo"], mode)


def kron_dense(factors):
    w = factors[0]
    for f in factors[1:]:
        w = jnp.kron(w, f)
    return w


def ffn(p, x, mode):
    w1, w3, w2 = (kron_dense(p[k]["factors"]) for k in ("w1", "w3", "w2"))
    h = (jax.nn.silu(einsum("bsd,df->bsf", x, w1, mode))
         * einsum("bsd,df->bsf", x, w3, mode))
    return einsum("bsf,fd->bsd", h, w2, mode)


def loss(cfg, params, tokens, labels, mode="highest"):
    """Mean next-token cross-entropy over every position."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    stack = params["stack"]["pos0"]
    for layer in range(stack["ln1"].shape[0]):
        lp = jax.tree.map(lambda a: a[layer], stack)
        x = x + attention(cfg, lp["mixer"], rms(x, lp["ln1"], eps), mode)
        x = x + ffn(lp["ffn"], rms(x, lp["ln2"], eps), mode)
    h = rms(x, params["final_norm"], eps).reshape(-1, x.shape[-1])
    head = params["embed"].T if cfg["tie_word_embeddings"] else params["lm_head"]
    labels = labels.reshape(-1)

    @jax.checkpoint
    def block_nll(hb, yb):
        logits = einsum("td,dv->tv", hb, head, mode)[:, : cfg["vocab_size"]]
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0])

    n = h.shape[0]
    chunk = math.gcd(n, CE_CHUNK)
    total = sum(block_nll(h[i:i + chunk], labels[i:i + chunk])
                for i in range(0, n, chunk))
    return total / n


def lr_at(opt: dict, step):
    """Linear warm-up, then cosine decay to ``min_lr_ratio`` of the peak."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + jnp.cos(math.pi * prog)))


def _is_norm(path) -> bool:
    return any(getattr(k, "key", None) in NORMS for k in path)


@functools.partial(jax.jit, static_argnames=("cfg_items", "opt_items", "mode"),
                   donate_argnums=(0, 1, 2))
def _step(params, m, v, step, tokens, labels, *, cfg_items, opt_items, mode):
    """One AdamW step: global-norm clipping, bias-corrected moments, decoupled
    weight decay on weight matrices (not on norm scales)."""
    cfg, opt = dict(cfg_items), dict(opt_items)
    store = jax.tree.leaves(params)[0].dtype
    val, grads = jax.value_and_grad(
        lambda p: loss(cfg, p, tokens, labels, mode))(
            jax.tree.map(lambda a: a.astype(jnp.float32), params))
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
        p = p.astype(jnp.float32)
        u = (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
        if not _is_norm(path):
            u = u + opt["weight_decay"] * p
        return (p - lr * u).astype(store)

    params = jax.tree_util.tree_map_with_path(update, params, m, v)
    g_norms = jax.tree.map(lambda g: jnp.linalg.norm(g.ravel()), grads)
    return params, m, v, val, g_norms


def train_steps(cfg: dict, opt: dict, params, batches, mode: str = "highest",
                dtype=jnp.bfloat16) -> dict:
    """Run ``len(batches)`` reference steps from ``params``.

    Returns the loss of every step, every leaf's gradient norm at the first
    step (clipped, as the optimizer takes it) and every leaf's change over
    all the steps, each keyed by the leaf's path."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if not isinstance(v, (dict, list))))
    opt_items = tuple(sorted(opt.items()))
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    start = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches):
        params, m, v, val, g_norms = _step(
            params, m, v, jnp.int32(i), tokens, labels,
            cfg_items=cfg_items, opt_items=opt_items, mode=mode)
        losses.append(float(val))
        if first is None:
            first = g_norms
    del m, v
    change = jax.tree.map(
        lambda a, b: jnp.linalg.norm((a.astype(jnp.float32)
                                      - b.astype(jnp.float32)).ravel()),
        params, start)
    flat = lambda t: {jax.tree_util.keystr(p): float(x)  # noqa: E731
                      for p, x in jax.tree_util.tree_leaves_with_path(t)}
    return {"loss": losses, "grad_norm": flat(first), "change_norm": flat(change)}
