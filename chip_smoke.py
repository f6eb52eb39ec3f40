#!/usr/bin/env python
"""Smoke test of the Kron-Matmul engine on one TPU chip (or a 2x2 host).

    python chip_smoke.py              # one chip: Kron-Matmul + training phases
    python chip_smoke.py --four-chips # only the (1, 4) mesh KronOp vs device 0

One process drives the chip through the entry points a user calls:

* Kron-Matmul phase: ``KronOp`` forward and ``jax.grad`` (x and factors) at
  paper Table 4 rows 2, 6, 15, 18, 22, 26 and 28, uncapped, in f32, plus row
  18 in bf16.  Each result is compared, on the same chip, with
  ``x @ kron_matrix(fs)`` (or the shuffle algorithm where the dense matrix
  does not fit) at ``precision=HIGHEST``.
* Training phase: qwen3-4b at its published widths with ``kron_ffn``, bf16
  and AdamW, depth cut from 36 to 2 layers, through ``elastic_mesh`` /
  ``train_state_init`` / ``make_train_step`` for 5 steps of ``SyntheticLM``
  (batch 4, seq 512).

The last line of standard output is ``{"ok": true, "device": {...}}`` when
every phase passed; otherwise the script exits non-zero and prints no such
line.  It refuses to run anywhere but a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0

# (Table 4 row, M, factor P dims, factor Q dims) — benchmarks/fig10.py.
TABLE4 = [
    (2, 20, (512,), (512,)),
    (6, 10, (52, 65), (50, 20)),
    (15, 16, (8,) * 3, (8,) * 3),
    (18, 1024, (4,) * 7, (4,) * 7),
    (22, 1526, (4,) * 6, (4,) * 6),
    (26, 16, (16,) * 6, (16,) * 6),
    (28, 16, (64,) * 3, (64,) * 3),
]
# Rows whose every stage must run as a compiled Pallas kernel.
MUST_BE_PALLAS = {15, 18, 26, 28}
BF16_ROW = 18
TOL = {"float32": 1e-4, "bfloat16": 2e-2}  # relative Frobenius error
DENSE_MAX_ELEMS = 1 << 26  # materialize kron(fs) for the reference up to this
REF_CHUNK_ELEMS = 1 << 24  # rows x cols per chunk of a factor-gradient reference
# (tag, batch of per-sample factors or None, M, factor dims) on the (1, 4) mesh.
FOUR_CHIP_CASES = [
    ("single M=16 (16,16)^6", None, 16, (16,) * 6),
    ("batched B=8 M=256 (16,16)^4", 8, 256, (16,) * 4),
]


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(a, b) -> float:
    import jax.numpy as jnp

    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def kernel_calls(hlo: str) -> dict:
    from repro.kernels.emit import KERNEL_NAMES

    out = {"tpu_custom_call": hlo.count("tpu_custom_call")}
    for name in KERNEL_NAMES:
        out[name] = hlo.count(name)
    return out


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Kron-Matmul phase
# ---------------------------------------------------------------------------


def _problem(m, ps, qs, dtype, seed):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), len(ps) + 2)
    k = math.prod(ps)
    x = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    fs = tuple(
        (jax.random.normal(kk, (p, q), jnp.float32) / math.sqrt(p)).astype(dtype)
        for kk, p, q in zip(keys[1:], ps, qs)
    )
    ct = jax.random.normal(keys[-1], (m, math.prod(qs)), jnp.float32).astype(dtype)
    return x, fs, ct


def _value_and_grads(f):
    import jax
    import jax.numpy as jnp

    def loss(x, fs, ct):
        y = f(x, fs)
        return jnp.vdot(y.astype(jnp.float32), ct.astype(jnp.float32)), y

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))


def _factor_grad(i, x, fs, ct):
    """dF_i of <x @ kron(fs), ct>: apply every other factor along its own
    axis of x viewed as (M, P_1, ..., P_n), then contract with ct viewed as
    (M, Q_1, ..., Q_n) over all axes but factor i's.  Summed over chunks of
    rows: on a TPU every such view pads its two minor axes to (8, 128), up to
    8x the array, so a chunk holds at most ``REF_CHUNK_ELEMS`` elements."""
    import jax
    import jax.numpy as jnp

    m, n = x.shape[0], len(fs)
    rows = max(d for d in range(1, m + 1)
               if m % d == 0 and d * x.shape[1] <= max(REF_CHUNK_ELEMS, x.shape[1]))

    def chunk(acc, xc):
        xc, cc = xc
        t = xc.reshape((rows,) + tuple(f.shape[0] for f in fs))
        for k, f in enumerate(fs):
            if k != i:
                t = jnp.moveaxis(jnp.tensordot(t, f, axes=([k + 1], [0])), -1, k + 1)
        c = cc.reshape((rows,) + tuple(f.shape[1] for f in fs))
        axes = [a for a in range(n + 1) if a != i + 1]
        return acc + jnp.tensordot(t, c, axes=(axes, axes)), None

    split = lambda a: a.reshape(m // rows, rows, a.shape[1])  # noqa: E731
    acc = jnp.zeros(fs[i].shape, jnp.float32)
    return jax.lax.scan(chunk, acc, (split(x), split(ct)))[0]


def _reference(m, ps, qs, x, fs, ct):
    """f32 reference (y, dx, dfs) at HIGHEST precision on the same chip.

    Where kron(fs) fits, jax.grad of ``x @ kron_matrix(fs)``.  Otherwise the
    shuffle algorithm for y and (on the transposed factors) for dx, and one
    contraction per factor gradient, each its own program: differentiating
    the shuffle algorithm would keep every intermediate (about 13 GB for
    row 26)."""
    import functools

    import jax
    from repro.core.kron import kron_matmul_naive, kron_matmul_shuffle

    if math.prod(ps) * math.prod(qs) <= DENSE_MAX_ELEMS:
        def ref(x, fs):
            with jax.default_matmul_precision("highest"):
                return kron_matmul_naive(x, list(fs))

        (_, y), (dx, dfs) = _value_and_grads(ref)(x, fs, ct)
        return y, dx, dfs, "x @ kron_matrix(fs)"
    with jax.default_matmul_precision("highest"):
        shuffle = jax.jit(lambda x, fs: kron_matmul_shuffle(x, list(fs)))
        y = shuffle(x, fs)
        dx = shuffle(ct, tuple(f.T for f in fs))
        dfs = [
            jax.jit(functools.partial(_factor_grad, i))(x, fs, ct)
            for i in range(len(fs))
        ]
    return y, dx, dfs, "shuffle algorithm"


def kron_case(row, m, ps, qs, dtype_name, failures):
    import jax
    import jax.numpy as jnp
    from repro.core.engine import KronOp

    dtype = jnp.dtype(dtype_name)
    tag = f"row {row} M={m} P={list(ps)} Q={list(qs)} {dtype_name}"
    op = KronOp(ps, qs, m=m, dtype_bytes=dtype.itemsize)
    log(f"[kron] {tag} plan: {op.plan.describe()}")
    for stage, fwd, bwd in op.stage_executors(m, dtype):
        log(f"[kron] {tag}   stage {stage}: fwd={fwd} grad={bwd}")
    x, fs, ct = _problem(m, ps, qs, dtype, SEED + row)

    run = _value_and_grads(lambda x, fs: op(x, fs))
    t0 = time.perf_counter()
    compiled = run.lower(x, fs, ct).compile()
    t_compile = time.perf_counter() - t0
    calls = kernel_calls(compiled.as_text())
    (_, y), (dx, dfs) = compiled(x, fs, ct)
    jax.block_until_ready((y, dx, dfs))
    t0 = time.perf_counter()
    (_, y), (dx, dfs) = compiled(x, fs, ct)
    jax.block_until_ready((y, dx, dfs))
    t_step = time.perf_counter() - t0

    y_ref, dx_ref, dfs_ref, ref_name = _reference(
        m, ps, qs, x.astype(jnp.float32),
        tuple(f.astype(jnp.float32) for f in fs), ct.astype(jnp.float32),
    )
    errs = {
        "y": rel_err(y, y_ref),
        "dx": rel_err(dx, dx_ref),
        "dF": max(rel_err(a, b) for a, b in zip(dfs, dfs_ref)),
    }
    finite = all(
        bool(jnp.isfinite(a.astype(jnp.float32)).all()) for a in (y, dx, *dfs)
    )
    tol = TOL[dtype_name]
    ok = finite and all(e <= tol for e in errs.values())
    if row in MUST_BE_PALLAS and calls["tpu_custom_call"] == 0:
        ok = False
        failures.append(f"{tag}: no tpu_custom_call in the compiled program")
    log(
        f"[kron] {tag} err vs {ref_name} (HIGHEST, tol {tol:g}): "
        + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
        + f" finite={finite} compile_s={t_compile:.2f} step_s={t_step:.4f} "
        f"kernels={calls} -> {'PASS' if ok else 'FAIL'}"
    )
    if not ok and not any(f.startswith(tag) for f in failures):
        failures.append(f"{tag}: errors {errs} finite={finite}")
    del x, fs, ct, y, dx, dfs, y_ref, dx_ref, dfs_ref, compiled


def kron_phase(dev, failures):
    for row, m, ps, qs in TABLE4:
        kron_case(row, m, ps, qs, "float32", failures)
    row, m, ps, qs = next(r for r in TABLE4 if r[0] == BF16_ROW)
    kron_case(row, m, ps, qs, "bfloat16", failures)
    log(f"[kron] peak_bytes_in_use={peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# Training phase
# ---------------------------------------------------------------------------


def train_phase(dev, failures, steps=5, batch=4, seq=512, layers=2):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.engine import KronOp
    from repro.core.layers import KronLinearSpec
    from repro.data import SyntheticLM
    from repro.optim import OptConfig
    from repro.runtime.fault import elastic_mesh
    from repro.runtime.sharding import param_shardings, token_sharding
    from repro.train import (
        TrainState, make_train_step, opt_state_shardings, train_state_init,
    )

    full = get_config("qwen3-4b")
    cfg = dataclasses.replace(full, n_layers=layers, kron_ffn=True, dtype="bfloat16")
    log(
        f"[train] qwen3-4b d_model={cfg.d_model} heads={cfg.n_heads} "
        f"kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab}; cut: n_layers {full.n_layers} -> {cfg.n_layers} "
        f"(the only cut); kron_ffn=True dtype={cfg.dtype} optimizer=AdamW "
        f"batch={batch} seq={seq} steps={steps}"
    )
    for name, (d_in, d_out) in (("up", (cfg.d_model, cfg.d_ff)),
                                ("down", (cfg.d_ff, cfg.d_model))):
        spec = KronLinearSpec.balanced(d_in, d_out, cfg.kron_factors)
        op = KronOp(spec.ps, spec.qs, batch=batch, shared_factors=True,
                    dtype_bytes=2)
        for stage, fwd, bwd in op.stage_executors(seq, jnp.bfloat16):
            log(f"[train] kron_ffn {name} {list(spec.ps)}->{list(spec.qs)} "
                f"stage {stage}: fwd={fwd} grad={bwd}")
    opt_cfg = OptConfig(lr=1e-4, warmup_steps=2, decay_steps=steps)
    mesh = elastic_mesh(jax.device_count())
    log(f"[train] mesh {dict(mesh.shape)}")
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, batch=batch, seed=SEED)
    with jax.set_mesh(mesh):
        state = train_state_init(cfg, opt_cfg, jax.random.PRNGKey(SEED))
        p_shard = param_shardings(
            jax.eval_shape(lambda: state.params), mesh,
            tied_embed=cfg.tie_embeddings,
        )
        replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        state = TrainState(
            jax.device_put(state.params, p_shard),
            jax.device_put(state.opt, opt_state_shardings(state.opt, p_shard, replicated)),
            state.step,
        )
        n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(state.params))
        log(f"[train] params={n_params}")
        tok_sh = token_sharding(mesh, batch)

        def batch_at(i):
            toks, labels = data.global_batch(i)
            return {"tokens": jax.device_put(toks, tok_sh),
                    "labels": jax.device_put(labels, tok_sh)}

        step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0,))
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, batch_at(0)).compile()
        t_compile = time.perf_counter() - t0
        calls = kernel_calls(compiled.as_text())
        log(f"[train] compile_s={t_compile:.2f} kernels={calls}")
        if calls["tpu_custom_call"] == 0:
            failures.append("train: no tpu_custom_call in the compiled step")
        for i in range(steps):
            b = batch_at(i)
            t0 = time.perf_counter()
            state, metrics = compiled(state, b)
            loss = float(metrics["loss"])
            gnorm = float(metrics["grad_norm"])
            dt = time.perf_counter() - t0
            finite = math.isfinite(loss) and math.isfinite(gnorm)
            log(f"[train] step {i} loss={loss:.4f} grad_norm={gnorm:.4f} "
                f"step_s={dt:.3f} finite={finite}")
            if not finite:
                failures.append(f"train step {i}: loss={loss} grad_norm={gnorm}")
    log(f"[train] peak_bytes_in_use={peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def _shards(tag, arr):
    for sh in arr.addressable_shards:
        log(f"[4chip]   {tag} shard {tuple(sh.data.shape)} on {sh.device}")
    return {sh.device.id for sh in arr.addressable_shards}


def four_chip_phase(failures):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import sharded_input, sharded_input_batched
    from repro.core.engine import KronOp
    from repro.runtime.sharding import make_mesh

    devs = jax.devices()
    if len(devs) < 4:
        failures.append(f"--four-chips needs 4 devices, found {len(devs)}")
        return
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    rep = NamedSharding(mesh, P())
    for tag, b, m, ps in FOUR_CHIP_CASES:
        x, fs, _ = _problem(m if b is None else b * m, ps, ps, jnp.float32, SEED)
        if b is not None:
            x = x.reshape(b, m, -1)
            keys = jax.random.split(jax.random.PRNGKey(SEED + 1), len(ps))
            fs = tuple(jax.random.normal(k, (b, p, p), jnp.float32) / math.sqrt(p)
                       for k, p in zip(keys, ps))
        kw = {} if b is None else dict(batch=b, shared_factors=False)
        local = KronOp(ps, ps, m=m, **kw)
        x0 = jax.device_put(x, devs[0])
        fs0 = tuple(jax.device_put(f, devs[0]) for f in fs)
        y0 = jax.jit(lambda x, fs: local(x, fs))(x0, fs0)
        for n_slabs in (1, "auto"):
            op = KronOp(ps, ps, m=m, mesh=mesh, n_slabs=n_slabs, **kw)
            xs = sharded_input(x, mesh) if b is None else sharded_input_batched(x, mesh)
            fss = tuple(jax.device_put(f, rep) for f in fs)
            t0 = time.perf_counter()
            fn = jax.jit(lambda x, fs: op(x, fs)).lower(xs, fss).compile()
            t_compile = time.perf_counter() - t0
            y = fn(xs, fss)
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            y = fn(xs, fss)
            jax.block_until_ready(y)
            t_step = time.perf_counter() - t0
            log(f"[4chip] {tag} n_slabs={n_slabs} rounds={list(op.rounds)} "
                f"describe: {op.describe()}")
            ids_in = _shards("input", xs)
            ids_out = _shards("output", y)
            err = rel_err(jax.device_put(y, devs[0]), y0)
            ok = err <= TOL["float32"] and len(ids_in) == 4 and len(ids_out) == 4
            log(f"[4chip] {tag} n_slabs={n_slabs} err vs local op on {devs[0]}="
                f"{err:.3e} input devices={sorted(ids_in)} output devices="
                f"{sorted(ids_out)} compile_s={t_compile:.2f} step_s={t_step:.4f}"
                f" kernels={kernel_calls(fn.as_text())} -> "
                f"{'PASS' if ok else 'FAIL'}")
            if not ok:
                failures.append(f"4chip {tag} n_slabs={n_slabs}: err={err} "
                                f"devices in={ids_in} out={ids_out}")


# ---------------------------------------------------------------------------


def health_failures() -> list[str]:
    from repro.runtime import guard

    report = guard.health_report()
    out = []
    for key, h in report["ops"].items():
        if h.get("degraded_calls") or h.get("errors"):
            out.append(f"guard: {key} degraded: {h}")
    for name, count in report["events"].items():
        out.append(f"guard event {name} x{count}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4) mesh KronOp against device 0")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script: {e}",
              file=sys.stderr)
        return 2
    cache_dir = compile_cache.configure()

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to run",
              file=sys.stderr)
        return 3
    from repro.kernels import hardware

    spec = hardware.spec(dev.device_kind)  # an unknown TPU kind is an error
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {cache_dir}; peaks: {spec}")

    failures: list[str] = []
    phases = ([("four-chip", lambda: four_chip_phase(failures))] if args.four_chips
              else [("kron", lambda: kron_phase(dev, failures)),
                    ("train", lambda: train_phase(dev, failures))])
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:
            failures.append(f"phase {name} raised:\n{traceback.format_exc()}")
            log(failures[-1])
        log(f"[{name}] phase seconds={time.perf_counter() - t0:.1f}")
    failures += health_failures()
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        log(f"{len(failures)} failure(s)")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
