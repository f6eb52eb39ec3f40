"""Logical-axis sharding rules: param/optimizer/input/cache PartitionSpecs.

Scheme (MaxText-style FSDP x TP, pod axis folded into batch/FSDP):
  * batch           -> ("pod","data") when present, else "data"
  * TP (heads, d_ff, experts, vocab) -> "model"
  * FSDP (the non-TP matrix dim)     -> "data" (+"pod" when it must: 100B+)
  * everything guarded by divisibility — a rule that does not divide falls
    back axis-by-axis to replication, so ANY (cfg, mesh) pair lowers.

Roles are inferred from parameter path names, not per-arch tables, so new
architectures inherit sane shardings.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def _axes(mesh: Mesh) -> tuple[tuple[str, ...], str]:
    """Returns (batch/fsdp axes, tp axis)."""
    names = mesh.axis_names
    tp = "model" if "model" in names else names[-1]
    batch = tuple(n for n in names if n != tp)
    return batch, tp


def _size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _fit(mesh: Mesh, spec: tuple, shape: tuple[int, ...]) -> P:
    """Drop axes that do not divide their dim; keep the rest."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is not None and dim % _size(mesh, ax) == 0:
            out.append(ax)
        else:
            out.append(None)
    return P(*out)


# role patterns: last path component (or two) -> (spec builder)
_MATRIX_IN_OUT = re.compile(r"\b(wq|wk|wv|w1|w3|wz|wx|wb|wc|wdt)$")
_MATRIX_OUT_IN = re.compile(r"\b(wo|w2)$")


def param_spec(
    path: str, shape: tuple[int, ...], mesh: Mesh,
    *, fsdp_pods: bool = False, tied_embed: bool = False,
) -> P:
    """PartitionSpec for one parameter leaf, by path role + divisibility."""
    batch_axes, tp = _axes(mesh)
    fsdp = batch_axes if fsdp_pods else (batch_axes[-1],)
    fsdp = fsdp if len(fsdp) > 1 else fsdp[0]
    nd = len(shape)

    def lead_pad(spec: tuple) -> P:
        """Stacked (scan) leaves carry extra leading dims -> None."""
        pad = (None,) * (nd - len(spec))
        return _fit(mesh, pad + spec, shape)

    if "factors" in path:                    # KronLinear factors: tiny, replicate
        return lead_pad(())
    if path.endswith("embed"):
        # (V, D) with vocab over TP: the lookup lowers to a masked local
        # gather + one (B,S,D) psum per step, and for tied heads the table
        # is already V-sharded for the logits matmul.  (A D-over-TP table
        # would make the gather collective-free, but XLA 0.8's partitioner
        # emits invalid IR for the backward dynamic-slice in that layout —
        # see DESIGN.md §8 note.)
        return lead_pad((tp, None))
    if path.endswith("lm_head"):
        return lead_pad((fsdp, tp))          # (D, V)
    if path.endswith("router"):
        return lead_pad((fsdp, None))
    if re.search(r"\bew[123]$", path):       # MoE expert stacks (E, D, F)/(E, F, D)
        e = shape[-3]
        if e % _size(mesh, tp) == 0:
            return lead_pad((tp, fsdp, None))   # expert parallelism
        # TP inside each expert instead (Mixtral: 8 experts < 16-way model)
        if path.endswith("ew2"):
            return lead_pad((None, tp, fsdp))
        return lead_pad((None, fsdp, tp))
    if path.endswith("conv_w"):
        return lead_pad((None, tp))
    if _MATRIX_OUT_IN.search(path):
        return lead_pad((tp, fsdp))
    if _MATRIX_IN_OUT.search(path):
        return lead_pad((fsdp, tp))
    if nd >= 2:
        return lead_pad((fsdp, tp))
    # 1-D (biases, norms, A/D/dt): TP only if the dim divides
    if shape and shape[-1] % _size(mesh, tp) == 0 and shape[-1] >= 1024:
        return lead_pad((tp,))
    return lead_pad(())


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_shardings(
    params_shape: Any, mesh: Mesh,
    *, fsdp_pods: bool = False, tied_embed: bool = False,
) -> Any:
    """Pytree of NamedShardings matching a pytree of ShapeDtypeStructs."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh,
            param_spec(_path_str(kp), leaf.shape, mesh,
                       fsdp_pods=fsdp_pods, tied_embed=tied_embed),
        ),
        params_shape,
    )


def batch_spec(mesh: Mesh) -> P:
    batch_axes, _ = _axes(mesh)
    ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    return P(ax)


def make_mesh(shape, axis_names, *, devices=None) -> Mesh:
    """A device mesh whose axes are all ``AxisType.Auto``: shardings are
    propagated by XLA, as this code base's constraints and shard_map bodies
    assume (``jax.make_mesh`` now defaults to explicit axes)."""
    return jax.make_mesh(
        tuple(shape), tuple(axis_names), devices=devices,
        axis_types=(AxisType.Auto,) * len(tuple(shape)),
    )


def ambient_mesh():
    """The mesh set by ``jax.set_mesh`` around the current trace, if any (an
    ``AbstractMesh`` inside ``jit``: axis names and sizes, no devices)."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def constrain_like_params(tree: Any) -> Any:
    """Pin a params-shaped pytree (gradients, accumulators) to the params'
    sharding rules.  Without this, XLA's backward pass is free to choose
    layouts for the scan's stacked-gradient accumulators — observed to pick
    partially-replicated ones that inflate per-device memory 3x+.
    No-op outside a mesh context."""
    mesh = ambient_mesh()
    if mesh is None:
        return tree
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: jax.lax.with_sharding_constraint(
            leaf, param_spec(_path_str(kp), leaf.shape, mesh)
        ),
        tree,
    )


def tp_size() -> int:
    """Model-axis size of the ambient mesh (1 outside a mesh context)."""
    mesh = ambient_mesh()
    if mesh is None:
        return 1
    _, tp = _axes(mesh)
    return _size(mesh, tp)


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """Mesh-agnostic activation sharding constraint.

    ``logical`` names one role per dim: None (unsharded), "batch"
    ((pod,data)), or "tp" ("model").  No-op outside a mesh context and for
    non-dividing dims, so model code can call it unconditionally — the
    pinned scan carries / logits are what keep XLA's SPMD propagation from
    inventing pathological reshards (observed: involuntary full remat on
    the layer-stack carry).
    """
    mesh = ambient_mesh()
    if mesh is None:
        return x
    batch_axes, tp = _axes(mesh)
    bax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    spec = []
    for dim, role in zip(x.shape, logical):
        ax = {"batch": bax, "tp": tp, None: None}[role]
        if ax is not None and dim % _size(mesh, ax) == 0:
            spec.append(ax)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def token_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    """(B, S) tokens: batch over (pod, data) if divisible."""
    batch_axes, _ = _axes(mesh)
    ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    if batch % _size(mesh, ax) == 0:
        return NamedSharding(mesh, P(ax, None))
    if batch % _size(mesh, batch_axes[-1]) == 0:
        return NamedSharding(mesh, P(batch_axes[-1], None))
    return NamedSharding(mesh, P(None, None))


def cache_spec(path: str, shape: tuple[int, ...], mesh: Mesh, batch: int) -> P:
    """KV / SSM cache leaves.

    Batch-shardable (decode_32k): (..., B, L, Hkv, hd) -> batch over data.
    B == 1 (long_500k): shard the cache LENGTH over the batch axes —
    flash-decoding-style sequence parallelism; XLA inserts the softmax
    reductions.
    """
    batch_axes, tp = _axes(mesh)
    bax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    nd = len(shape)
    leaf = path.rsplit("/", 1)[-1]

    def lead_pad(spec: tuple) -> P:
        pad = (None,) * (nd - len(spec))
        return _fit(mesh, pad + spec, shape)

    if leaf in ("k", "v"):
        if batch % _size(mesh, bax) == 0:
            return lead_pad((bax, None, None, tp))
        return lead_pad((None, bax, None, tp))   # sequence-parallel cache
    if leaf in ("k_scale", "v_scale"):           # int8-KV scales (B,L,Hkv,1)
        if batch % _size(mesh, bax) == 0:
            return lead_pad((bax, None, None, None))
        return lead_pad((None, bax, None, None))
    if leaf == "pos":
        return lead_pad(())
    if leaf == "conv":                           # (B, w-1, conv_dim)
        if batch % _size(mesh, bax) == 0:
            return lead_pad((bax, None, tp))
        return lead_pad((None, None, tp))
    if leaf == "h":                              # (B, H, N, P)
        if batch % _size(mesh, bax) == 0:
            return lead_pad((bax, tp, None, None))
        return lead_pad((None, tp, None, None))
    return lead_pad(())


def cache_shardings(cache_shape: Any, mesh: Mesh, batch: int) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh, cache_spec(_path_str(kp), leaf.shape, mesh, batch)
        ),
        cache_shape,
    )


__all__ = [
    "param_spec",
    "param_shardings",
    "cache_spec",
    "cache_shardings",
    "token_sharding",
    "batch_spec",
]
