"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together every substrate: config -> mesh (elastic to whatever devices
exist) -> sharded init (or checkpoint restore, cross-mesh) -> synthetic data
pipeline -> jitted train_step (FSDP x TP, microbatch accumulation) ->
straggler monitor -> atomic async checkpoints.

On this CPU container use ``--reduced`` (tiny same-family config, 1 device).
On a real pod, remove ``--reduced`` and launch one process per host; the
same code path lowers the full config onto the production mesh (proven by
dryrun.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..configs import get_config
from ..data import SyntheticLM
from ..models.config import reduced as reduce_cfg
from ..optim import OptConfig, ShampooConfig, state_memory_report
from ..runtime import compile_cache, guard, telemetry
from ..runtime.events import get_logger
from ..runtime.fault import StragglerMonitor, elastic_mesh
from ..runtime.sharding import param_shardings, token_sharding
from ..train import (
    TrainState, make_train_step, opt_state_shardings, train_state_init,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config for CPU demo runs")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--want-model-parallel", type=int, default=16)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--kron-ffn", action="store_true",
                    help="enable the paper's Kron-compressed FFN projections")
    ap.add_argument("--optimizer", choices=("adamw", "shampoo"),
                    default="adamw",
                    help="shampoo: Kron-factored preconditioning applied "
                         "through batched KronOp shape groups (docs/optim.md)")
    ap.add_argument("--precond-every", type=int, default=20,
                    help="shampoo inverse-root refresh cadence (steps)")
    ap.add_argument("--numerics", choices=list(guard.NUMERICS_POLICIES),
                    default=None,
                    help="non-finite guard at StageProgram boundaries "
                         "(default: FASTKRON_NUMERICS or off); training "
                         "typically wants raise — fail fast and restart from "
                         "the last checkpoint before the divergence")
    ap.add_argument("--telemetry", metavar="OUT.jsonl", default=None,
                    help="KronScope JSONL event sink: spans, guard/chaos "
                         "events, step-latency histograms, tokens/s gauges")
    ap.add_argument("--trace", metavar="OUT.trace.json", default=None,
                    help="Chrome-trace (Perfetto) export of the host-side "
                         "spans, written at exit")
    args = ap.parse_args()
    compile_cache.configure()
    if args.numerics is not None:
        guard.set_numerics_policy(args.numerics)
    if args.telemetry or args.trace:
        telemetry.configure(jsonl=args.telemetry, trace=args.trace)
    log = get_logger("repro.train")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg, dtype="float32")
    if args.kron_ffn:
        from dataclasses import replace

        cfg = replace(cfg, kron_ffn=True)
    opt_kw = dict(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                  decay_steps=args.steps)
    if args.optimizer == "shampoo":
        opt_cfg: OptConfig = ShampooConfig(
            precond_every=args.precond_every, **opt_kw
        )
    else:
        opt_cfg = OptConfig(**opt_kw)

    mesh = elastic_mesh(jax.device_count(),
                        want_model=args.want_model_parallel)
    print(f"mesh: {dict(mesh.shape)} devices={jax.device_count()}")

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    mgr = CheckpointManager(args.ckpt_dir, keep=3, async_save=True) \
        if args.ckpt_dir else None

    with jax.set_mesh(mesh):
        state = train_state_init(cfg, opt_cfg, jax.random.PRNGKey(0))
        p_shard = param_shardings(
            jax.eval_shape(lambda: state.params), mesh,
            tied_embed=cfg.tie_embeddings,
        )
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        opt_shard = opt_state_shardings(state.opt, p_shard, replicated)
        state = TrainState(
            jax.device_put(state.params, p_shard),
            jax.device_put(state.opt, opt_shard),
            state.step,
        )
        start = 0
        if mgr and args.resume and mgr.latest_step() is not None:
            restored = mgr.restore(state._asdict())
            state = TrainState(**restored)
            start = int(state.step)
            print(f"resumed from step {start}")

        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg, microbatches=args.microbatches),
            donate_argnums=(0,),
        )
        tok_sh = token_sharding(mesh, args.batch)
        mon = StragglerMonitor(action="log")
        shampoo_on = isinstance(opt_cfg, ShampooConfig)
        base_step_s = None  # rolling min of non-refresh steps (see below)
        t_start = time.time()
        for i in range(start, args.steps):
            toks, labels = data.global_batch(i)
            batch = {
                "tokens": jax.device_put(toks, tok_sh),
                "labels": jax.device_put(labels, tok_sh),
            }
            mon.start()
            t_step = time.perf_counter()
            with telemetry.span("train_step", step=i):
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            dt_step = time.perf_counter() - t_step
            telemetry.observe("train.step_seconds", dt_step)
            if shampoo_on and telemetry.active():
                telemetry.gauge_set(
                    "optim.precond_stale_steps",
                    int(metrics["precond_stale_steps"]),
                )
                # the refresh is fused into the jitted step (lax.cond), so
                # its cost is observed as the refresh-step excess over the
                # rolling minimum of plain steps
                opt_step = int(state.opt["step"])
                is_refresh = (
                    opt_step == 1
                    or opt_step % max(opt_cfg.precond_every, 1) == 0
                )
                if not is_refresh and i > start:
                    base_step_s = (
                        dt_step if base_step_s is None
                        else min(base_step_s, dt_step)
                    )
                elif is_refresh and base_step_s is not None:
                    telemetry.observe(
                        "optim.root_refresh_seconds",
                        max(0.0, dt_step - base_step_s),
                    )
            mon.stop(i)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(
                    f"step {i:5d} loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"lr={float(metrics['lr']):.2e}",
                    flush=True,
                )
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, state._asdict())
        if mgr:
            mgr.save(args.steps, state._asdict())
            mgr.wait()
    dt = time.time() - t_start
    tok_s = args.steps * args.batch * args.seq / max(dt, 1e-9)
    telemetry.gauge_set("train.tokens_per_s", tok_s)
    log.info(f"done: {args.steps} steps in {dt:.1f}s ({tok_s:.0f} tok/s)")
    # Optimizer-state memory by dtype: makes the bf16 ``state_dtype``
    # saving (and Shampoo's kron-statistics footprint) visible at exit.
    mem = state_memory_report(state.opt)
    log.info(
        f"optimizer state: {mem['total_bytes'] / 1e6:.2f} MB "
        + " ".join(
            f"{k}={v / 1e6:.2f}MB" for k, v in sorted(mem["by_dtype"].items())
        )
    )
    # ONE merged exit report: guard health carries the telemetry snapshot
    # (counters, gauges, histogram percentiles) when KronScope is live.
    report = guard.health_report()
    report["opt_state_memory"] = mem
    if telemetry.active() or report["events"] or any(
        h["degraded_calls"] or h["errors"] for h in report["ops"].values()
    ):
        log.info(f"health: {report}")
    telemetry.shutdown()


if __name__ == "__main__":
    main()
