"""The causal attention core's two executors: the Pallas flash kernel
(``kernels/flash_attention.py``, here in interpret mode) against the chunked
XLA path on output and on dq, dk, dv, at qwen3-4b's GQA layout and
DeepSeek-V2-Lite's latent layout; and the rule in
``attention._flash_block`` that picks one, with its trace-time counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention
from repro.models import attention, mla
from repro.models import model as M
from repro.models.config import ModelConfig, YarnConfig
from repro.runtime import telemetry

YARN = YarnConfig(factor=40, original_max_position_embeddings=4096, beta_fast=32,
                  beta_slow=1, mscale_all_dim=0.707)


def _cfg(**over):
    base = dict(name="attn-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=96, vocab=64, vocab_pad_multiple=32,
                dtype="float32")
    return ModelConfig(**{**base, **over})


# (id, B, S, H, Hkv, Dqk, Dv, denom, dtype, tolerance): qwen3-4b's 32/8 heads
# of 128; DeepSeek-V2-Lite's 16 latent heads of 192/128 with YaRN's scale.
_MLA_CFG = _cfg(family="moe", n_heads=16, n_kv_heads=16, kv_lora_rank=32,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                rope_scaling=YARN)
LAYOUTS = [
    ("gqa", 2, 256, 32, 8, 128, 128, None, jnp.float32, 2e-5),
    ("mla", 1, 256, 16, 16, 192, 128, 1.0 / mla.softmax_scale(_MLA_CFG), jnp.float32, 2e-5),
    ("gqa_bf16", 2, 256, 32, 8, 128, 128, None, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize(
    "b,s,h,hkv,dqk,dv,denom,dtype,tol", [c[1:] for c in LAYOUTS],
    ids=[c[0] for c in LAYOUTS],
)
def test_kernel_matches_the_chunked_xla_core(b, s, h, hkv, dqk, dv, denom, dtype, tol):
    """Blocks of 128 at S = 256: one block skipped above the diagonal, two
    masked on it and one whole below, in every pass."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, dqk)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dqk)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dv)).astype(dtype)
    ct = jax.random.normal(ks[3], (b, s, h, dv)).astype(dtype)
    cfg = _cfg(n_heads=h, n_kv_heads=hkv, head_dim=dqk)
    scale = 1.0 / (np.sqrt(dqk) if denom is None else denom)

    def kernel(q, k, v):
        return flash_attention.causal_flash_attention(q, k, v, scale=scale, block=128,
                                                      interpret=True)

    def xla(q, k, v):
        return attention.causal_attention(cfg, q, k, v, jnp.arange(s), q_chunk=128,
                                          denom=denom)

    def f32(a):
        return np.asarray(a.astype(jnp.float32))

    got, got_vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(xla, q, k, v)
    assert got.dtype == want.dtype == dtype
    for name, g, w in zip(("out", "dq", "dk", "dv"), (got, *got_vjp(ct)), (want, *want_vjp(ct))):
        w = f32(w)
        err = np.abs(f32(g) - w).max() / np.abs(w).max()
        assert err < tol, (name, err)


def _counted(fn, *args):
    """Trace ``fn`` and return (counters, whether a Pallas call was traced)."""
    telemetry.configure()
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.reset()
    return counters, "pallas_call" in str(jaxpr)


def _core(cfg, s, arange_positions=True):
    q = jnp.zeros((1, s, cfg.n_heads, cfg.head_dim_))
    k = jnp.zeros((1, s, cfg.n_kv_heads, cfg.head_dim_))

    def fn(q, k):
        return attention.causal_attention(cfg, q, k, k, jnp.arange(s),
                                          arange_positions=arange_positions)

    return fn, (q, k)


RULE = [
    # (id, on the TPU, config overrides, S, positions stated 0..S-1, kernel?)
    ("kernel", True, {}, 256, True, True),
    ("cpu_backend", False, {}, 256, True, False),
    ("sliding_window", True, {"sliding_window": 64}, 256, True, False),
    ("s_not_a_block_multiple", True, {}, 200, True, False),
    ("s_over_a_block_not_a_multiple", True, {}, 640, True, False),
    ("positions_not_stated", True, {}, 256, False, False),
]


@pytest.mark.parametrize("on_tpu,over,s,arange_positions,kernel", [c[1:] for c in RULE],
                         ids=[c[0] for c in RULE])
def test_rule_picks_the_executor_and_counts_it(monkeypatch, on_tpu, over, s,
                                               arange_positions, kernel):
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    fn, args = _core(_cfg(**over), s, arange_positions)
    counters, pallas = _counted(fn, *args)
    assert pallas == kernel
    assert counters.get("attn.core.kernel", 0) == int(kernel)
    assert counters.get("attn.core.xla", 0) == int(not kernel)


def test_rule_keeps_the_xla_path_on_a_mesh(monkeypatch):
    """A Pallas call is one device's program: on a mesh of more than one
    device (where the context-parallel path lives) the core stays XLA."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    fn, args = _core(_cfg(), 256)
    mesh = jax.sharding.AbstractMesh((2, 1), ("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh):
        counters, pallas = _counted(fn, *args)
    assert not pallas
    assert counters.get("attn.core.xla", 0) == 1


@pytest.mark.parametrize("latent", [False, True], ids=["gqa", "mla"])
def test_model_forward_states_its_positions(monkeypatch, latent):
    """``model.forward`` builds positions ``0..S-1`` and says so: on the TPU
    every attention layer's core is the kernel, none the XLA path."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(_MLA_CFG, family="dense") if latent else _cfg()
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jnp.zeros((1, 128), jnp.int32)
    counters, pallas = _counted(lambda p, t: M.forward(cfg, p, t)[0], params, tokens)
    assert pallas
    assert counters.get("attn.core.kernel", 0) >= 1
    assert counters.get("attn.core.xla", 0) == 0


def test_core_is_scoped_in_the_compiled_program():
    """``model.attn.core`` names the core's instructions (metadata only)."""
    fn, args = _core(_cfg(), 256)
    assert "kronscope.model.attn.core" in jax.jit(fn).lower(*args).as_text(debug_info=True)
