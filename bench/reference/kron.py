"""Plain reference of ``Y = X (F_1 x ... x F_N)`` and its gradients.

X's columns are read as the digits ``(p_1, ..., p_N)``, most significant
first, so Y is X contracted with each factor along its own digit: the
shuffle algorithm, one einsum per factor (taken from the bring-up's
``chip_smoke.py``).  dX is the same chain on the transposed factors, and
dF_i contracts X with every other factor applied against the cotangent over
every digit but the i-th.  Everything runs in blocks of rows, so that the
largest Table 4 shapes fit one chip beside the program's own arrays.

A dF_i entry sums rows x prod(P)/P_i products (16.7M at Table 4 row 26): a
float32 sum that long rounds by about as much as a lower-precision product
does.  So the comparison keeps the rows and two more digits apart in each
block's contraction (partial sums of a few thousand products) and adds the
partials in float64 on the host.
"""
from __future__ import annotations

import functools
import string

import jax
import jax.numpy as jnp
import numpy as np

from .numerics import einsum

BLOCK_ELEMS = 1 << 24  # elements of X in one block of rows


def block_rows(m: int, k: int, limit: int = BLOCK_ELEMS) -> int:
    """The largest divisor of ``m`` whose block of rows holds at most
    ``limit`` elements (one row at least)."""
    return max(d for d in range(1, m + 1) if m % d == 0 and d * k <= max(limit, k))


def _apply(t, f, axis: int, mode: str):
    """Contract digit ``axis`` of ``t`` (rows first) with ``f`` (P, Q)."""
    letters = string.ascii_letters[: t.ndim]
    src = letters
    dst = letters[:axis] + "Z" + letters[axis + 1:]
    return einsum(f"{src},{letters[axis]}Z->{dst}", t, f, mode)


def forward(x, fs, mode: str = "highest"):
    """``x @ kron(fs)`` for x ``(rows, prod P)``."""
    t = x.reshape((x.shape[0],) + tuple(f.shape[0] for f in fs))
    for i, f in enumerate(fs):
        t = _apply(t, f, i + 1, mode)
    return t.reshape(x.shape[0], -1)


KEPT_DIGITS = 2  # digits besides the rows left out of a block's dF sum


def factor_grad(i: int, x, fs, ct, mode: str = "highest", keep: int = 0):
    """dF_i of ``<x @ kron(fs), ct>``; with ``keep``, its partial sums with
    the rows and the first ``keep`` other digits left uncontracted (leading
    axes of the result)."""
    t = x.reshape((x.shape[0],) + tuple(f.shape[0] for f in fs))
    for j, f in enumerate(fs):
        if j != i:
            t = _apply(t, f, j + 1, mode)
    c = ct.reshape((ct.shape[0],) + tuple(f.shape[1] for f in fs))
    letters = string.ascii_letters[: t.ndim]
    kept = ""
    if keep:
        kept = letters[0] + "".join(letters[a] for a in range(1, t.ndim) if a != i + 1)[:keep]
    spec = f"{letters},{letters[:i + 1]}Z{letters[i + 2:]}->{kept}{letters[i + 1]}Z"
    return einsum(spec, t, c, mode)


@functools.partial(jax.jit, static_argnames=("grad", "mode"))
def _block(x, fs, ct, ys, dxs, *, grad: bool, mode: str):
    """One block of rows: for each answer, the squared gaps of its Y (and
    dX) from the reference; the reference's squared norms; and the partial
    sums of every reference dF_i over the block."""
    x = x.astype(jnp.float32)
    fs = tuple(f.astype(jnp.float32) for f in fs)
    sq = lambda a: jnp.sum(jnp.square(a))  # noqa: E731
    y_ref = forward(x, fs, mode)
    out = {"y": ([sq(y.astype(jnp.float32) - y_ref) for y in ys], sq(y_ref))}
    parts = ()
    if grad:
        ct = ct.astype(jnp.float32)
        dx_ref = forward(ct, tuple(f.T for f in fs), mode)
        out["dx"] = ([sq(dx.astype(jnp.float32) - dx_ref) for dx in dxs], sq(dx_ref))
        parts = tuple(factor_grad(i, x, fs, ct, mode, keep=KEPT_DIGITS)
                      for i in range(len(fs)))
    return out, parts


def compare(x, fs, answers, *, ct=None, mode: str = "highest",
            rows: int | None = None, put=lambda a: a) -> dict:
    """The widest relative Frobenius gap, over ``answers`` (each ``(y, dx,
    dfs)``), of Y and, given the cotangent ``ct``, of dX and the worst dF_i,
    from the reference computed once at ``mode``, block by block.  ``put``
    moves a block (a slice of a possibly sharded array) to where the
    reference runs."""
    m, k = x.shape
    ys = [a[0] for a in answers]
    rows = rows or block_rows(m, max(k, ys[0].shape[1]))
    grad = ct is not None
    fs_r = tuple(put(f) for f in fs)
    acc = [np.zeros(f.shape, np.float64) for f in fs_r] if grad else []
    gaps: dict[str, list] = {}
    refs: dict[str, float] = {}
    for r0 in range(0, m, rows):
        sl = slice(r0, r0 + rows)
        blk, parts = _block(
            put(x[sl]), fs_r, put(ct[sl]) if grad else None,
            [put(y[sl]) for y in ys],
            [put(a[1][sl]) for a in answers] if grad else None,
            grad=grad, mode=mode)
        for a, part in zip(acc, parts):
            part = np.asarray(part, np.float64)
            a += part.reshape((-1,) + a.shape).sum(axis=0)
        for name, (gap, ref) in blk.items():
            g = gaps.setdefault(name, [0.0] * len(answers))
            for j, v in enumerate(gap):
                g[j] += float(v)
            refs[name] = refs.get(name, 0.0) + float(ref)
    out = {f"{name}_err": max((v / max(refs[name], 1e-30)) ** 0.5 for v in g)
           for name, g in gaps.items()}
    if grad:
        out["df_err"] = max(
            float(np.linalg.norm(np.asarray(d, np.float64) - r)
                  / max(np.linalg.norm(r), 1e-30))
            for a in answers for d, r in zip(a[2], acc))
    return out


_forward = jax.jit(forward, static_argnames=("mode",))
_factor_grad = jax.jit(factor_grad, static_argnums=(0,), static_argnames=("mode",))


def control_outputs(x, fs, ct=None, mode: str = "high", rows: int | None = None,
                    put=lambda a: a, keep=lambda a: a):
    """The reference at ``mode`` in the program's place: (y, dx, dfs), made
    block by block where ``put`` moves the inputs, and each block of Y and
    dX kept where ``keep`` moves it (``np.asarray``: on the host, for
    answers too large for the one chip the blocks are made on)."""
    m, k = x.shape
    rows = rows or block_rows(m, k)
    fs_r = tuple(put(f).astype(jnp.float32) for f in fs)
    ys, dxs = [], []
    dfs = [jnp.zeros(f.shape, jnp.float32) for f in fs_r] if ct is not None else None
    for r0 in range(0, m, rows):
        xb = put(x[r0:r0 + rows]).astype(jnp.float32)
        ys.append(keep(_forward(xb, fs_r, mode=mode)))
        if ct is not None:
            cb = put(ct[r0:r0 + rows]).astype(jnp.float32)
            dxs.append(keep(_forward(cb, tuple(f.T for f in fs_r), mode=mode)))
            for i in range(len(fs_r)):
                dfs[i] = dfs[i] + _factor_grad(i, xb, fs_r, cb, mode=mode)
    cat = np.concatenate if isinstance(ys[0], np.ndarray) else jnp.concatenate
    y = cat(ys)
    if ct is None:
        return y, None, None
    return y, cat(dxs), tuple(dfs)
