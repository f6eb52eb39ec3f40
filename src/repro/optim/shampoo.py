"""Shampoo-style Kron-factored preconditioning through the KronOp engine.

The preconditioned update ``P = L^{-1/4} G R^{-1/4}`` is a Kron-Matmul:
with the row-major flattening ``vec_row(A^T G B) = vec_row(G) @ (A (x) B)``,
every layer's apply is one row of ``x @ (Lroot (x) Rroot)`` — exactly the
workload the engine accelerates.  So the application step groups same-shape
layers and executes ONE per-sample-factor batched ``KronOp`` call per shape
group (``engine.kron_precond_op``): x = the stacked update directions
reshaped ``(B, 1, p*q)``, factors = the stacked per-layer root pairs
``(B, p, p)`` / ``(B, q, q)``.  Because the inverse roots are symmetric,
``Lroot^T u Rroot = L^{-1/4} u R^{-1/4}``.

Algorithm per step (mirrors ``adamw.opt_update`` bit-for-bit up to the
direction swap, so ineligible params get EXACTLY AdamW):

1. statistics ``L += G G^T``, ``R += G^T G`` (or EMA with ``stats_beta``)
   from the clipped gradient, stored in ``state_dtype`` (bf16 option);
2. on a slow cadence (``precond_every``) refresh the inverse quarter roots
   by eigendecomposition or coupled Newton (``root_method``) inside the
   jitted step via ``lax.cond`` — never a mid-training re-plan;
3. precondition the ADAM direction ``u = m^/(sqrt(v^)+eps)`` through the
   shape-grouped batched op, then **graft** the AdamW step size back:
   ``u_sh = P * ||u|| / ||P||``.  Identity roots therefore reproduce the
   grafted-AdamW step exactly — which is also the degradation target:
   a failed/stale/ill-conditioned refresh flips the layer's ``ok`` flag
   and the step falls back to ``u`` for the interval (guard event
   ``root_refresh_degraded``, chaos site ``root_refresh``).

Eligibility (the rank shortlist): 2-D params with both dims > 1 and
max dim <= ``max_precond_dim`` — embeddings/LM heads (vocab-sized) and
1-D norms/biases fall back to plain AdamW.  Stacked per-layer 3-D leaves
``(S, p, q)`` (the scan-over-periods layout) are S independent layers and
feed S samples into their shape group.

State layout: ``{"m", "v", "step"}`` mirror AdamW (same NamedShardings, so
FSDP/ZeRO-3 partitioning applies unchanged) plus a ``"kron"`` subtree keyed
by ``/``-joined param paths holding per-layer ``l/r`` statistics,
``lroot/rroot`` inverse roots, ``ok`` validity flags and ``stale`` step
counters — replicated (small: 2(p^2+q^2) per layer vs p*q params).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..runtime import chaos, guard, telemetry
from .adamw import OptConfig, global_norm, lr_at, opt_init, opt_update, _quantize

_TINY = 1e-30  # graft-ratio denominator floor: never divides by exact zero


@dataclass(frozen=True)
class ShampooConfig(OptConfig):
    """AdamW knobs plus the Kron-preconditioner cadence/conditioning knobs."""

    precond_every: int = 20      # inverse-root refresh cadence (steps)
    stats_beta: float = 0.95     # EMA on L/R; 1.0 = classic sum accumulation
    matrix_eps: float = 1e-2     # relative ridge (damped whitening; the
                                 # reduced-config sweep in EXPERIMENTS.md
                                 # §Optim shows small ridges over-whiten)
    root_method: str = "eigh"    # "eigh" | "newton" (coupled iteration)
    newton_iters: int = 25       # coupled-Newton iterations
    max_precond_dim: int = 1024  # rank shortlist: larger dims fall to AdamW
    min_precond_dim: int = 4     # smaller dims (stacked norms/biases) too


# ---------------------------------------------------------------------------
# Eligibility / shape grouping
# ---------------------------------------------------------------------------


def _leaf_path(keypath) -> str:
    """``/``-joined path string for a pytree leaf (checkpoint-style keys)."""
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _eligible(shape, cfg: ShampooConfig):
    """``(S, p, q)`` for a precondition-eligible leaf shape, else None.

    2-D ``(p, q)`` leaves are one layer (S=1); 3-D ``(S, p, q)`` leaves are
    S stacked layers (the scan-over-layer-periods parameter layout).  The
    ``min_precond_dim`` floor keeps stacked norm/bias vectors — which
    flatten to ``(n_layers, d)`` 2-D leaves — on the plain-AdamW path.
    """
    if len(shape) == 2:
        s, (p, q) = 1, shape
    elif len(shape) == 3:
        s, p, q = shape
    else:
        return None
    if min(p, q) < cfg.min_precond_dim or max(p, q) > cfg.max_precond_dim:
        return None
    return int(s), int(p), int(q)


def shape_groups(params: Any, cfg: ShampooConfig) -> dict:
    """``{(p, q): [(path, S), ...]}`` over precondition-eligible leaves.

    Deterministic (pytree flatten order).  Each group becomes ONE batched
    per-sample ``KronOp`` call of batch ``sum(S)`` in the update.
    """
    groups: dict = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        spq = _eligible(leaf.shape, cfg)
        if spq is None:
            continue
        s, p, q = spq
        groups.setdefault((p, q), []).append((_leaf_path(kp), s))
    return groups


def prewarm(params: Any, cfg: ShampooConfig) -> tuple:
    """Construct the shape-group ops before the first jitted step.

    Mirrors ``train.steps.prebuild_kron_ops``: handles land in the engine's
    bounded memo so the first trace reuses resolved plans instead of
    re-planning mid-training.  ``params`` may be real arrays or
    ``jax.eval_shape`` structs."""
    from ..core.engine import kron_precond_op

    ops = []
    for (p, q), members in shape_groups(params, cfg).items():
        b = sum(s for _, s in members)
        ops.append(kron_precond_op(p, q, b))
    return tuple(ops)


# ---------------------------------------------------------------------------
# Inverse quarter roots
# ---------------------------------------------------------------------------


def _ridge_of(s: jax.Array, eps: float) -> jax.Array:
    """Relative ridge ``eps * lambda_max-upper-bound`` (the symmetric
    inf-norm), with an absolute floor so all-zero statistics still produce
    a scalar-multiple-of-identity root — which grafting maps to exactly the
    AdamW step.  Relative-to-lambda_max caps the post-ridge condition
    number at ~1/eps, which is what keeps the f32 coupled-Newton iteration
    convergent on the rank-deficient statistics of early training (an EMA
    of a few gradient outer products)."""
    lam = jnp.max(jnp.sum(jnp.abs(s), axis=-1))
    return eps * jnp.maximum(lam, eps)


def _root_eigh(s: jax.Array, eps: float) -> tuple[jax.Array, jax.Array]:
    """``(S^{-1/4}, ok)`` by eigendecomposition of one ``(d, d)`` statistic."""
    d = s.shape[-1]
    s = (s + s.T) * 0.5
    ridge = _ridge_of(s, eps)
    w, v = jnp.linalg.eigh(s + ridge * jnp.eye(d, dtype=s.dtype))
    ok = jnp.isfinite(w).all() & jnp.isfinite(v).all() & (w[-1] > 0)
    w = jnp.maximum(w, ridge * jnp.finfo(s.dtype).eps)
    root = (v * (w ** -0.25)) @ v.T
    root = (root + root.T) * 0.5
    ok = ok & jnp.isfinite(root).all()
    return root, ok


def _root_newton(s: jax.Array, eps: float, iters: int) -> tuple[jax.Array, jax.Array]:
    """``(S^{-1/4}, ok)`` by the coupled-Newton iteration for inverse p-th
    roots (p=4): ``X <- X T, M <- T^p M`` with ``T = ((p+1)I - M)/p``,
    converging to ``(zS)^{-1/p}`` for ``z = 1/||S||``."""
    p = 4
    d = s.shape[-1]
    s = (s + s.T) * 0.5
    ridge = _ridge_of(s, eps)
    a = s + ridge * jnp.eye(d, dtype=s.dtype)
    z = 1.0 / jnp.maximum(jnp.linalg.norm(a), _TINY)
    eye = jnp.eye(d, dtype=s.dtype)

    def body(_, xm):
        x, m = xm
        t = ((p + 1) * eye - m) / p
        t2 = t @ t
        return x @ t, (t2 @ t2) @ m

    x, m = jax.lax.fori_loop(0, iters, body, (eye, z * a))
    root = x * (z ** (1.0 / p))
    root = (root + root.T) * 0.5
    ok = (
        jnp.isfinite(root).all()
        # converged: M -> I (the coupled invariant); loose gate, the graft
        # fallback catches anything this lets through
        & (jnp.abs(m - eye).max() < 0.1)
    )
    return root, ok


def inverse_quarter_root(
    stat: jax.Array, *, eps: float = 1e-2, method: str = "eigh", iters: int = 25
) -> tuple[jax.Array, jax.Array]:
    """``(S^{-1/4}, ok)`` for a stacked ``(S, d, d)`` (or ``(d, d)``) PSD
    statistic; ``ok`` is a per-layer validity flag (finite, converged)."""
    if method == "eigh":
        fn = lambda m: _root_eigh(m, eps)
    elif method == "newton":
        fn = lambda m: _root_newton(m, eps, iters)
    else:
        raise guard.PlanError(
            f"unknown root_method {method!r}: want 'eigh' or 'newton'"
        )
    if stat.ndim == 2:
        return fn(stat)
    return jax.vmap(fn)(stat)


# ---------------------------------------------------------------------------
# Preconditioner application (the KronOp hot path)
# ---------------------------------------------------------------------------


def _groups_of_kron(kron: dict) -> dict:
    """Shape groups recovered from the kron state subtree (stable order)."""
    groups: dict = {}
    for path in kron:
        s, p, _ = kron[path]["lroot"].shape
        q = kron[path]["rroot"].shape[-1]
        groups.setdefault((p, q), []).append((path, s))
    return groups


def precondition(updates: dict, kron: dict, *, looped: bool = False) -> dict:
    """Apply ``Lroot^T u Rroot`` to every layer: ``{path: (S, p, q)}`` in,
    same-keyed dict out.

    ``looped=False``: ONE per-sample batched ``KronOp`` per shape group over
    the stacked layers — the headline path.  ``looped=True``: one single-
    sample op call per layer — the reference the batched path must match
    bitwise (tiles never split the contraction dim, so the summation order
    is identical; pinned in tests/test_optim.py and raced in
    benchmarks/fig_optim.py).
    """
    from ..core.engine import kron_precond_op

    out: dict = {}
    for (p, q), members in _groups_of_kron(kron).items():
        if looped:
            op = kron_precond_op(p, q, 1)
            for path, s in members:
                u = updates[path].reshape(s, 1, 1, p * q)
                lr_ = kron[path]["lroot"]
                rr_ = kron[path]["rroot"]
                ys = [
                    op(u[i], (lr_[i : i + 1], rr_[i : i + 1]))
                    for i in range(s)
                ]
                out[path] = jnp.concatenate(ys, axis=0).reshape(s, p, q)
            continue
        b = sum(s for _, s in members)
        x = jnp.concatenate(
            [updates[path].reshape(s, 1, p * q) for path, s in members], axis=0
        )
        ls = jnp.concatenate([kron[path]["lroot"] for path, _ in members], 0)
        rs = jnp.concatenate([kron[path]["rroot"] for path, _ in members], 0)
        y = kron_precond_op(p, q, b)(x, (ls, rs)).reshape(b, p, q)
        off = 0
        for path, s in members:
            out[path] = y[off : off + s]
            off += s
    return out


# ---------------------------------------------------------------------------
# init / update
# ---------------------------------------------------------------------------


def shampoo_init(params: Any, cfg: ShampooConfig) -> dict:
    """AdamW state (m/v mirror params -> same shardings) plus the ``kron``
    subtree.  Roots start at identity with ``ok=True``: the first interval
    IS the grafted-AdamW step, so warmup needs no special casing."""
    state = opt_init(params, cfg)
    sd = jnp.dtype(cfg.state_dtype)
    kron: dict = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        spq = _eligible(leaf.shape, cfg)
        if spq is None:
            continue
        s, p, q = spq
        eye = lambda d, dt: jnp.tile(jnp.eye(d, dtype=dt)[None], (s, 1, 1))
        kron[_leaf_path(kp)] = {
            "l": jnp.zeros((s, p, p), sd),
            "r": jnp.zeros((s, q, q), sd),
            "lroot": eye(p, jnp.float32),
            "rroot": eye(q, jnp.float32),
            "ok": jnp.ones((s,), bool),
            "stale": jnp.zeros((s,), jnp.int32),
        }
    state["kron"] = kron
    return state


def _refresh_leaf(entry: dict, l32, r32, refresh, cfg: ShampooConfig):
    """New ``(lroot, rroot, ok, did, n_bad)`` for one leaf's stacked layers.

    ``lax.cond`` keeps the eigh/Newton work off the non-refresh steps; a
    chaos-injected ``NumericsError`` (site ``root_refresh``) degrades the
    leaf to its grafted-AdamW fallback for the interval — recorded in guard
    health, never crashing the step."""
    s = entry["ok"].shape[0]
    try:
        chaos.maybe_fail("root_refresh")
    except guard.NumericsError as e:
        guard.record_event("root_refresh_degraded", e)
        guard.warn_once(
            ("root_refresh", "chaos"),
            f"shampoo: inverse-root refresh failed ({e}) — layer degraded "
            f"to grafted AdamW for this interval",
        )
        return (
            entry["lroot"], entry["rroot"],
            jnp.zeros((s,), bool), jnp.zeros((s,), bool),
            jnp.zeros((), jnp.int32),
        )

    def do(args):
        l, r, lroot, rroot = args
        nl, okl = inverse_quarter_root(
            l, eps=cfg.matrix_eps, method=cfg.root_method,
            iters=cfg.newton_iters,
        )
        nr, okr = inverse_quarter_root(
            r, eps=cfg.matrix_eps, method=cfg.root_method,
            iters=cfg.newton_iters,
        )
        ok = okl & okr
        sel = ok[:, None, None]
        return (
            jnp.where(sel, nl, lroot),
            jnp.where(sel, nr, rroot),
            ok,
            ok,
            jnp.sum(~ok).astype(jnp.int32),
        )

    def keep(args):
        _, _, lroot, rroot = args
        return (
            lroot, rroot, entry["ok"], jnp.zeros((s,), bool),
            jnp.zeros((), jnp.int32),
        )

    return jax.lax.cond(
        refresh, do, keep, (l32, r32, entry["lroot"], entry["rroot"])
    )


def _report_refresh_failures(n_bad, policy: str) -> None:
    """Host-side numerics report (``jax.debug.callback`` target)."""
    n = int(n_bad)
    if n <= 0:
        return
    msg = (
        f"shampoo inverse-root refresh produced {n} invalid root pair(s) "
        f"(non-finite or non-positive statistics) — affected layers "
        f"degraded to grafted AdamW until the next refresh"
    )
    guard.record_event("root_refresh_degraded", guard.NumericsError(msg))
    if policy == "raise":
        raise guard.NumericsError(msg)
    guard.warn_once(("root_refresh", "nonfinite"), f"kron guard: {msg}")


def shampoo_update(
    grads: Any, state: dict, params: Any, cfg: ShampooConfig
) -> tuple[Any, dict, dict]:
    """Returns ``(new_params, new_state, metrics)`` — the AdamW contract.

    Ineligible leaves run the exact AdamW update; eligible leaves swap the
    Adam direction for its grafted Kron-preconditioned image (one batched
    ``KronOp`` call per shape group).
    """
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

    if cfg.compress:
        compensated = jax.tree.map(lambda g, e: g + e, grads, state["err"])
        quant = jax.tree.map(lambda g: _quantize(g, cfg.compress), compensated)
        new_err = jax.tree.map(lambda c, q: c - q, compensated, quant)
        grads = quant
    else:
        new_err = state.get("err")

    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)

    step = state["step"] + 1
    lr = lr_at(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    sd = jnp.dtype(cfg.state_dtype)
    kron = state["kron"]
    refresh = (step == 1) | (step % max(cfg.precond_every, 1) == 0)

    # Adam moments + direction for EVERY leaf (ineligible leaves stop here).
    flat = jax.tree_util.tree_flatten_with_path(params)
    paths = [_leaf_path(kp) for kp, _ in flat[0]]
    treedef = flat[1]
    flat_p = [l for _, l in flat[0]]
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    new_m, new_v, u_adam = [], [], []
    for g, m, v in zip(flat_g, flat_m, flat_v):
        m32 = m.astype(jnp.float32) * b1 + g * (1 - b1)
        v32 = v.astype(jnp.float32) * b2 + g * g * (1 - b2)
        new_m.append(m32)
        new_v.append(v32)
        u_adam.append((m32 / bc1) / (jnp.sqrt(v32 / bc2) + cfg.eps))

    # Statistics + amortized root refresh for the eligible leaves.
    new_kron: dict = {}
    n_bad = jnp.zeros((), jnp.int32)
    with telemetry.scope("optim.root_refresh"):
        for i, path in enumerate(paths):
            if path not in kron:
                continue
            entry = kron[path]
            s, p, _ = entry["l"].shape
            q = entry["r"].shape[-1]
            g3 = flat_g[i].reshape(s, p, q)
            ggt = jnp.einsum("spq,skq->spk", g3, g3)
            gtg = jnp.einsum("spq,spk->sqk", g3, g3)
            l32 = entry["l"].astype(jnp.float32)
            r32 = entry["r"].astype(jnp.float32)
            if cfg.stats_beta >= 1.0:
                l32, r32 = l32 + ggt, r32 + gtg
            else:
                bs = cfg.stats_beta
                l32 = l32 * bs + ggt * (1 - bs)
                r32 = r32 * bs + gtg * (1 - bs)
            lroot, rroot, ok, did, bad = _refresh_leaf(
                entry, l32, r32, refresh, cfg
            )
            n_bad = n_bad + bad
            new_kron[path] = {
                "l": l32.astype(sd),
                "r": r32.astype(sd),
                "lroot": lroot,
                "rroot": rroot,
                "ok": ok,
                "stale": jnp.where(did, 0, entry["stale"] + 1),
            }

    # Same contract as guard.check_finite: policy read at trace time, eager
    # values report synchronously (raise raises on the spot), traced values
    # report through jax.debug.callback when the step is consumed.
    policy = guard.numerics_policy()
    if policy != "off":
        if isinstance(n_bad, jax.core.Tracer):
            jax.debug.callback(
                lambda nb, p=policy: _report_refresh_failures(nb, p), n_bad
            )
        else:
            _report_refresh_failures(int(n_bad), policy)

    # Shape-grouped batched preconditioning of the Adam direction + graft.
    u_final = list(u_adam)
    if new_kron:
        with telemetry.scope("optim.precondition"):
            idx = {path: i for i, path in enumerate(paths)}
            shapes = {
                path: (e["ok"].shape[0], e["l"].shape[-1], e["r"].shape[-1])
                for path, e in new_kron.items()
            }
            updates = {
                path: u_adam[idx[path]].reshape(shapes[path])
                for path in new_kron
            }
            pre = precondition(updates, new_kron)
            for path, y3 in pre.items():
                u3 = updates[path]
                unorm = jnp.sqrt(jnp.sum(u3 * u3, axis=(1, 2)))
                pnorm = jnp.sqrt(jnp.sum(y3 * y3, axis=(1, 2)))
                grafted = y3 * (unorm / (pnorm + _TINY))[:, None, None]
                # runtime fallback: stale/failed roots OR a degenerate
                # apply (zero/non-finite norm) -> the grafted-AdamW step
                ok = (
                    new_kron[path]["ok"]
                    & jnp.isfinite(pnorm)
                    & (pnorm > 0)
                )
                u_final[idx[path]] = jnp.where(
                    ok[:, None, None], grafted, u3
                ).reshape(u_adam[idx[path]].shape)

    new_params = []
    for p_, u in zip(flat_p, u_final):
        if p_.ndim >= 2:  # decay matrices only, exactly as AdamW
            u = u + cfg.weight_decay * p_.astype(jnp.float32)
        new_params.append((p_.astype(jnp.float32) - lr * u).astype(p_.dtype))

    new_state = {
        "m": jax.tree.unflatten(treedef, [m.astype(sd) for m in new_m]),
        "v": jax.tree.unflatten(treedef, [v.astype(sd) for v in new_v]),
        "step": step,
        "kron": new_kron,
    }
    if cfg.compress:
        new_state["err"] = new_err
    stale = (
        jnp.max(jnp.concatenate([e["stale"] for e in new_kron.values()]))
        if new_kron
        else jnp.zeros((), jnp.int32)
    )
    metrics = {
        "grad_norm": gnorm,
        "lr": lr,
        "precond_stale_steps": stale,
        "precond_ok_frac": (
            jnp.mean(
                jnp.concatenate(
                    [e["ok"] for e in new_kron.values()]
                ).astype(jnp.float32)
            )
            if new_kron
            else jnp.ones(())
        ),
    }
    return (
        jax.tree.unflatten(treedef, new_params),
        new_state,
        metrics,
    )


# ---------------------------------------------------------------------------
# Dispatch + reporting
# ---------------------------------------------------------------------------


def opt_for(cfg: OptConfig) -> tuple[Callable, Callable]:
    """``(init_fn, update_fn)`` for a config: ``ShampooConfig`` routes to
    the Kron-preconditioned path, plain ``OptConfig`` to AdamW."""
    if isinstance(cfg, ShampooConfig):
        return shampoo_init, shampoo_update
    return opt_init, opt_update


def state_memory_report(opt_state: Any) -> dict:
    """``{"total_bytes", "by_dtype": {dtype: bytes}}`` over an optimizer
    state pytree — the launcher's exit-report line that makes the bf16
    ``state_dtype`` saving (and the kron subtree's footprint) visible."""
    by: dict[str, int] = {}
    for leaf in jax.tree.leaves(opt_state):
        dt = jnp.dtype(leaf.dtype)
        by[dt.name] = by.get(dt.name, 0) + int(leaf.size) * dt.itemsize
    return {"total_bytes": sum(by.values()), "by_dtype": by}


__all__ = [
    "ShampooConfig",
    "shampoo_init",
    "shampoo_update",
    "opt_for",
    "shape_groups",
    "prewarm",
    "precondition",
    "inverse_quarter_root",
    "state_memory_report",
]
