"""Train / prefill / serve step builders.

``make_train_step`` returns a pure (state, batch) -> (state, metrics)
function: CE loss + MoE aux, grads, AdamW update.  ``microbatches > 1``
accumulates gradients over a ``lax.scan`` of batch slices — the activation-
memory lever that lets 100B+ configs fit the 256-chip dry-run mesh.

``make_serve_step`` is the decode-shape entry point the dry run lowers for
``decode_32k`` / ``long_500k`` (one new token against a KV/SSM cache).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..models import model as M
from ..models.config import ModelConfig
from ..optim.adamw import OptConfig
from ..optim.shampoo import ShampooConfig, opt_for
from ..optim import shampoo as _shampoo
from ..runtime import telemetry
from ..runtime.sharding import constrain_like_params


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jax.Array


def prebuild_kron_ops(
    cfg: ModelConfig, *, batch: int | None = None, seq_len: int | None = None,
    mesh=None, prefill_shapes: Sequence[tuple[int, int]] = (),
    decode_batch: int | None = None, opt_cfg: OptConfig | None = None,
) -> tuple:
    """Construct the ``KronOp`` handles behind every Kron-compressed
    projection in ``cfg`` before the first jitted step.

    With ``batch`` and ``seq_len`` given (serving knows both), the plan for
    the ``(batch*seq_len)``-row collapsed problem is resolved HERE — the
    tile search lands in the engine's shared bounded plan memo, which is
    exactly what the layer applies hit at trace time, so the first trace
    does no Python-side planning.  Without them (training builds steps
    before seeing a batch) this constructs and returns the op handles;
    their plans resolve once, on first call, through the same shared memo.
    ``mesh``: also pre-validate the distributed ops a ``kron_distributed``
    scope would route to (shapes the mesh cannot host are skipped — the
    scope falls back to the local path for those).

    ``prefill_shapes``: extra ``(batch, seq_len)`` pairs to pre-resolve —
    the continuous-batching engine prefills each padding bucket at its own
    shape, and a shape missing here re-plans at trace time mid-serve (the
    PR-8 fix; tests/test_serve_engine.py pins zero steady-state misses).
    ``decode_batch``: also resolve the decode-step shape (rows = slots*1).
    ``opt_cfg``: with a ``ShampooConfig``, ALSO construct the optimizer's
    shape-grouped preconditioner-apply ops (one batched per-sample op per
    same-shape layer group, sized from ``jax.eval_shape`` of the params) —
    the training analogue of the serving prewarm, so the first train step
    never plans a preconditioner op mid-trace.
    """
    opt_ops: tuple = ()
    if isinstance(opt_cfg, ShampooConfig):
        import functools
        shapes = jax.eval_shape(
            functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)
        )
        opt_ops = _shampoo.prewarm(shapes, opt_cfg)
    if not getattr(cfg, "kron_ffn", False):
        return opt_ops
    from ..core.engine import kron_op_for
    from ..core.layers import KronLinearSpec

    dtype_bytes = {"bfloat16": 2, "float16": 2, "float64": 8}.get(
        str(getattr(cfg, "dtype", "float32")), 4
    )
    up = KronLinearSpec.balanced(cfg.d_model, cfg.d_ff, cfg.kron_factors)
    down = KronLinearSpec.balanced(cfg.d_ff, cfg.d_model, cfg.kron_factors)
    shapes: list[tuple[int, int]] = []
    if batch is not None and seq_len is not None:
        shapes.append((int(batch), int(seq_len)))
    shapes.extend((int(b), int(s)) for b, s in prefill_shapes)
    if decode_batch is not None:
        shapes.append((int(decode_batch), 1))
    ops = []
    for spec in (up, down):
        for b, s in dict.fromkeys(shapes):
            # A serving shape: (B, T, d) collapses to B*T rows — resolve
            # that plan now (m is rows per sample for a batched op).
            ops.append(kron_op_for(
                spec.ps, spec.qs, m=s, batch=b,
                shared_factors=True, dtype_bytes=dtype_bytes,
            ))
        if not shapes:
            ops.append(kron_op_for(spec.ps, spec.qs))
        if mesh is not None:
            try:
                ops.append(kron_op_for(spec.ps, spec.qs, mesh=mesh))
            except ValueError:
                pass  # no legal round schedule — scope will run local
    return tuple(ops) + opt_ops


def train_state_init(cfg: ModelConfig, opt_cfg: OptConfig, key: jax.Array) -> TrainState:
    params = M.init_params(cfg, key)
    init_fn, _ = opt_for(opt_cfg)
    return TrainState(params, init_fn(params, opt_cfg), jnp.zeros((), jnp.int32))


def opt_state_shardings(opt_state: Any, param_shardings: Any, replicated) -> Any:
    """Shardings for an optimizer-state pytree: ``m``/``v``/``err`` mirror
    the parameter shardings (FSDP'd params => ZeRO-3 partitioned state),
    everything else (``step``, Shampoo's ``kron`` statistics subtree) is
    replicated — the kron subtree is ``O(p^2 + q^2)`` per layer, small next
    to the ``p*q`` parameters it preconditions."""
    out = {}
    for key in opt_state:
        if key in ("m", "v", "err"):
            out[key] = param_shardings
        else:
            out[key] = jax.tree.map(lambda _: replicated, opt_state[key])
    return out


def loss_fn(
    cfg: ModelConfig,
    params: Any,
    tokens: jax.Array,
    labels: jax.Array,
    embeds: jax.Array | None = None,
):
    """Mean next-token cross-entropy plus the MoE balance loss, weighted by
    the configuration's ``moe.aux_weight``."""
    aux_weight = cfg.moe.aux_weight if cfg.moe is not None else 0.0
    logits, aux = M.forward(cfg, params, tokens, embeds)
    n_fe = cfg.n_frontend_tokens if embeds is not None else 0
    with telemetry.scope("model.head"):
        logits = logits[:, n_fe:, :]
        ll = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(ll, labels[..., None], axis=-1).mean()
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptConfig,
    *,
    microbatches: int = 1,
    with_embeds: bool = False,
    acc_dtype=jnp.float32,
):
    """Returns train_step(state, batch) -> (state, metrics).

    batch: dict(tokens (B,S), labels (B,S)[, embeds (B,n_fe,D)]).
    ``acc_dtype``: gradient-accumulator dtype (bf16 halves the buffer for
    100B+ models; error < 2^-8 relative per add, fine for <=32 microbatches).
    """
    # Construct the op handles up front (model projections AND, for a
    # ShampooConfig, the optimizer's shape-group preconditioner ops); their
    # plans resolve once through the shared bounded memo (the first trace
    # reuses, not re-plans).
    prebuild_kron_ops(cfg, opt_cfg=opt_cfg)
    _, update_fn = opt_for(opt_cfg)

    def grads_of(params, tokens, labels, embeds):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, labels, embeds), has_aux=True
        )(params)
        return loss, parts, constrain_like_params(grads)

    def train_step(state: TrainState, batch: dict):
        params = state.params
        tokens, labels = batch["tokens"], batch["labels"]
        embeds = batch.get("embeds") if with_embeds else None

        if microbatches == 1:
            loss, parts, grads = grads_of(params, tokens, labels, embeds)
        else:
            b = tokens.shape[0]
            mb = b // microbatches

            def split(x):
                return x.reshape(microbatches, mb, *x.shape[1:])

            mb_batch = (split(tokens), split(labels),
                        split(embeds) if embeds is not None else None)

            def acc_body(carry, xs):
                g_acc, l_acc = carry
                t, l, e = xs
                loss, _, grads = grads_of(params, t, l, e)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), g_acc, grads
                )
                return (g_acc, l_acc + loss), None

            g0 = constrain_like_params(
                jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype), params)
            )
            if mb_batch[2] is None:
                xs = (mb_batch[0], mb_batch[1],
                      jnp.zeros((microbatches, 0), jnp.float32))
                def acc_body2(carry, x):
                    t, l, _ = x
                    return acc_body(carry, (t, l, None))
                (grads, loss), _ = jax.lax.scan(acc_body2, (g0, 0.0), xs)
            else:
                (grads, loss), _ = jax.lax.scan(acc_body, (g0, 0.0), mb_batch)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            parts = {"nll": loss, "aux": jnp.zeros((), jnp.float32)}

        with telemetry.scope("optim.update"):
            new_params, new_opt, opt_metrics = update_fn(
                grads, state.opt, params, opt_cfg
            )
        metrics = {"loss": loss, **parts, **opt_metrics}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, max_len: int, *, with_embeds: bool = False):
    def prefill_step(params, tokens, embeds=None):
        return M.prefill(cfg, params, tokens, max_len,
                         embeds if with_embeds else None)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """serve_step(params, cache, tokens (B,1), pos) -> (next_token_logits, cache)."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = M.decode_step(cfg, params, cache, tokens, pos)
        return logits, cache

    return serve_step


__all__ = [
    "TrainState",
    "train_state_init",
    "prebuild_kron_ops",
    "opt_state_shardings",
    "loss_fn",
    "make_train_step",
    "make_prefill_step",
    "make_serve_step",
]
