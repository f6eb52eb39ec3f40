"""Contractions of the plain references, at a stated precision.

``highest`` is float32 (``Precision.HIGHEST``): the reference.  The other
modes are the controls, the same reference one precision step lower:

* ``high``: three bf16 passes (``hi*hi + hi*lo + lo*hi``), which is what
  ``Precision.HIGH`` does on a TPU; written out so that the CPU, which
  ignores precision flags, computes the same thing;
* ``fp8``: operands and, in the backward pass, cotangents cast to
  ``float8_e4m3fn`` with a per-tensor scale, accumulated in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "fp8")
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _to_fp8(a):
    a = a.astype(_F32)
    scale = _FP8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(_F32) / scale


@jax.custom_vjp
def _q_operand(a):
    """fp8 forward, cotangent passed through."""
    return _to_fp8(a)


_q_operand.defvjp(lambda a: (_to_fp8(a), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_cotangent(y):
    """Identity forward, fp8 cotangent: the backward products take fp8."""
    return y


_q_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_to_fp8(g),))


def einsum(spec: str, a, b, mode: str = "highest"):
    """``jnp.einsum(spec, a, b)`` in float32 at the precision ``mode`` names."""
    if mode == "highest":
        return jnp.einsum(spec, a.astype(_F32), b.astype(_F32),
                          precision=_HIGHEST, preferred_element_type=_F32)
    if mode == "high":
        def split(x):
            # reduce_precision, not a round trip through bfloat16: XLA may
            # drop a bf16 -> f32 round trip (excess precision), and on a
            # TPU that left lo at 0, one pass instead of three
            x = x.astype(_F32)
            hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
            return hi, jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)

        (ah, al), (bh, bl) = split(a), split(b)
        # bf16 values are exact in float32, so each pass is exact products
        # accumulated in float32, on any backend
        one = lambda x, y: jnp.einsum(spec, x, y, precision=_HIGHEST,  # noqa: E731
                                      preferred_element_type=_F32)
        return one(ah, bh) + one(ah, bl) + one(al, bh)
    if mode == "fp8":
        return _q_cotangent(jnp.einsum(spec, _q_operand(a), _q_operand(b),
                                       precision=_HIGHEST,
                                       preferred_element_type=_F32))
    raise ValueError(f"unknown precision mode {mode!r}; known: {MODES}")
