"""Causal flash attention for the TPU: one Pallas kernel forward, two backward.

``causal_flash_attention(q, k, v, scale=...)`` computes
``softmax(scale * q k^T + causal mask) v`` for q ``(B, S, H, Dqk)``, k
``(B, S, Hkv, Dqk)``, v ``(B, S, Hkv, Dv)`` with ``H % Hkv == 0`` (GQA: q
head ``h`` reads kv head ``h // (H // Hkv)``) and ``Dv`` free of ``Dqk``
(latent attention's 192/128).  Positions are ``0..S-1`` on both sides.

Each score tile lives in VMEM only: the forward keeps a running max and sum
per query row (online softmax) and writes the output and the row's
log-sum-exp; the backward recomputes each tile from them, ``dq`` in one
kernel (kv blocks innermost) and ``dk``/``dv`` in another (q heads of the
group and q blocks innermost, so a kv head's gradient is summed in VMEM).
Blocks wholly above the causal diagonal are skipped: their grid steps run no
instruction, and their index maps repeat the last block that is needed, so
no DMA fetches them.  Only blocks that straddle the diagonal are masked.

Precision is that of the XLA path's dots at DEFAULT precision on the TPU:
q, k, v, the probabilities and ``ds`` enter the MXU in the inputs' dtype with
float32 accumulation; ``scale`` multiplies the float32 logits; the softmax
statistics are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
# Finite, so that ``exp(MASK - m)`` is 0 and no ``inf - inf`` can appear.
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
BLOCK = 512  # rows of a q or kv block; the sequence must be a multiple
KERNEL_NAMES = ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv")

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def block_for(s: int) -> int | None:
    """The q and kv block for sequence length ``s``, or None where the
    kernel cannot tile it: ``s`` must be a multiple of 128 lanes, and of
    ``BLOCK`` when longer."""
    if s % LANES:
        return None
    b = min(BLOCK, s)
    return b if s % b == 0 else None


def _compiler_params(interpret: bool, semantics: tuple[str, ...]):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    from . import hardware

    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(hardware.tpu_spec().vmem_bytes * 3 // 4),
    )


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _last_kv(i, bq, bk):
    """The last kv block that q block ``i`` attends to."""
    return (i * bq + bq - 1) // bk


def _first_q(j, bq, bk):
    """The first q block that attends to kv block ``j``."""
    return (j * bk) // bq


def _lanes(x, n):
    """A lane-replicated ``(rows, 128)`` column widened to ``n`` lanes."""
    return x[:, :1] if n % LANES else jnp.tile(x, (1, n // LANES))


def _when_blocks(needed, straddles, body):
    """Run ``body(masked)`` on a needed block: masked only where it
    straddles the diagonal."""

    @pl.when(needed & straddles)
    def _():
        body(True)

    @pl.when(needed & jnp.logical_not(straddles))
    def _():
        body(False)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, bq, bk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, MASK, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def body(masked):
        v = v_ref[...]
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, MASK)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))  # (bq, 128)
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
        m_sc[...] = m_next
        pv = lax.dot_general(p.astype(v.dtype), v, _NN,
                             preferred_element_type=jnp.float32)
        acc_sc[...] = _lanes(alpha, acc_sc.shape[-1]) * acc_sc[...] + pv

    _when_blocks(j * bk <= i * bq + bq - 1, j * bk + bk - 1 > i * bq, body)

    @pl.when(j == _last_kv(i, bq, bk))
    def _():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes(l, acc_sc.shape[-1])).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


def _forward(q, k, v, scale, bq, bk, interpret):
    """Head-major q (B,H,S,Dqk), k (B,Hkv,S,Dqk), v (B,Hkv,S,Dv) ->
    o (B,H,S,Dv), lse (B,H,S,128) lane-replicated float32."""
    b, h, s, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g = h // hkv
    nq, nk = s // bq, s // bk

    def kv_map(ib, ih, i, j):
        return ib, ih // g, jnp.minimum(j, _last_kv(i, bq, bk)), 0

    def q_map(ib, ih, i, j):
        return ib, ih, i, 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, dqk), q_map),
            pl.BlockSpec((None, None, bk, dqk), kv_map),
            pl.BlockSpec((None, None, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, dv), q_map),
            pl.BlockSpec((None, None, bq, LANES), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32),
        ],
        scratch_shapes=[_vmem((bq, LANES)), _vmem((bq, LANES)), _vmem((bq, dv))],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward: dq, then dk and dv
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_sc, *,
               scale, bq, bk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def body(masked):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, MASK)
        p = jnp.exp(s - _lanes(lse_ref[...], bk))
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_ref[...], bk))
        acc_sc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                       preferred_element_type=jnp.float32)

    _when_blocks(j * bk <= i * bq + bq - 1, j * bk + bk - 1 > i * bq, body)

    @pl.when(j == _last_kv(i, bq, bk))
    def _():
        dq_ref[...] = (acc_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, bq, bk, g):
    j, ig, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((ig == 0) & (i == 0))
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def body(masked):
        q, do = q_ref[...], do_ref[...]
        # transposed tiles, (kv rows, q columns): the row statistics broadcast
        # along sublanes and both gradients are plain products
        st = lax.dot_general(k_ref[...], q, _NT,
                             preferred_element_type=jnp.float32) * scale
        if masked:
            rows = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            cols = i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            st = jnp.where(rows <= cols, st, MASK)
        pt = jnp.exp(st - lse_ref[:1, :])
        dv_sc[...] += lax.dot_general(pt.astype(do.dtype), do, _NN,
                                      preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[:1, :])
        dk_sc[...] += lax.dot_general(dst.astype(q.dtype), q, _NN,
                                      preferred_element_type=jnp.float32)

    _when_blocks(i * bq + bq - 1 >= j * bk, j * bk + bk - 1 > i * bq, body)

    @pl.when((ig == g - 1) & (i == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, scale, bq, bk, interpret):
    b, h, s, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g = h // hkv
    nq, nk = s // bq, s // bk
    di = jnp.einsum("bhsd,bhsd->bhs", o.astype(jnp.float32), do.astype(jnp.float32))
    col = lambda x: jnp.broadcast_to(x[..., None], (b, h, s, LANES))  # noqa: E731
    row = lambda x: jnp.broadcast_to(x[:, :, None, :], (b, h, SUBLANES, s))  # noqa: E731

    def q_map(ib, ih, i, j):
        return ib, ih, i, 0

    def kv_map(ib, ih, i, j):
        return ib, ih // g, jnp.minimum(j, _last_kv(i, bq, bk)), 0

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, dqk), q_map),
            pl.BlockSpec((None, None, bk, dqk), kv_map),
            pl.BlockSpec((None, None, bk, dv), kv_map),
            pl.BlockSpec((None, None, bq, dv), q_map),
            pl.BlockSpec((None, None, bq, LANES), q_map),
            pl.BlockSpec((None, None, bq, LANES), q_map),
        ],
        out_specs=pl.BlockSpec((None, None, bq, dqk), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_vmem((bq, dqk))],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(q, k, v, do, lse, col(di))

    def qh_map(ib, ihkv, j, ig, i):
        return ib, ihkv * g + ig, jnp.maximum(i, _first_q(j, bq, bk)), 0

    def stat_map(ib, ihkv, j, ig, i):
        return ib, ihkv * g + ig, 0, jnp.maximum(i, _first_q(j, bq, bk))

    def kvh_map(ib, ihkv, j, ig, i):
        return ib, ihkv, j, 0

    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, g=g),
        grid=(b, hkv, nk, g, nq),
        in_specs=[
            pl.BlockSpec((None, None, bq, dqk), qh_map),
            pl.BlockSpec((None, None, bk, dqk), kvh_map),
            pl.BlockSpec((None, None, bk, dv), kvh_map),
            pl.BlockSpec((None, None, bq, dv), qh_map),
            pl.BlockSpec((None, None, SUBLANES, bq), stat_map),
            pl.BlockSpec((None, None, SUBLANES, bq), stat_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, dqk), kvh_map),
            pl.BlockSpec((None, None, bk, dv), kvh_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((bk, dqk)), _vmem((bk, dv))],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(q, k, v, do, row(lse[..., 0]), row(di))
    return dq, dk, dv_


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, block, interpret):
    return _forward(q, k, v, scale, block, block, interpret)[0]


def _flash_fwd(q, k, v, scale, block, interpret):
    o, lse = _forward(q, k, v, scale, block, block, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, block, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, scale, block, block, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_flash_attention(q, k, v, *, scale: float, block: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q (B,S,H,Dqk), k (B,S,Hkv,Dqk), v (B,S,Hkv,Dv) -> (B,S,H,Dv) in q's
    dtype: the causal softmax of ``scale * q k^T`` over positions ``0..S-1``
    times v.  ``block`` (default ``block_for(S)``) tiles both sequences."""
    s, h, hkv = q.shape[1], q.shape[2], k.shape[2]
    block = block_for(s) if block is None else block
    if block is None or s % block or h % hkv:
        raise ValueError(f"no causal flash tiling for S={s}, H={h}, Hkv={hkv}")
    heads = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    out = _flash(heads(q), heads(k), heads(v), float(scale), block, interpret)
    return heads(out)


__all__ = ["causal_flash_attention", "block_for", "KERNEL_NAMES", "BLOCK"]
