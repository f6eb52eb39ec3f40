"""Serving launcher: one-shot batch, or continuous batching (docs/serving.md).

    # one-shot (legacy): prefill ONE fixed batch, decode --gen tokens
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --batch 4 --prompt-len 32 --gen 16

    # continuous batching: open-loop Poisson arrivals through the pure
    # scheduler (launch/scheduler.py), bucketed prefill, slot recycling
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --arrival-rate 0.5 --requests 32 --gen 8 --kron-ffn

The continuous path is split in two layers.  ``launch.scheduler`` decides
(pure state machine, device-free); ``ServeEngine`` here executes — bucketed
prefill under the guard ladder (a ``VmemOverflowError`` on the grouped
prefill degrades to per-request prefills, never drops a request), admission
of prefilled requests into the in-flight decode batch via the slot-form
cache primitives (``model.cache_to_slots``/``cache_take``/``cache_put``),
and one fixed-shape decode step per scheduler step.  Every (batch-bucket,
len-bucket) prefill shape and the decode shape map to pre-resolved per-shape
``KronOp`` plans (``train.prebuild_kron_ops``, prewarmed at startup), so
steady-state serving does zero re-planning.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..data import SyntheticLM
from ..models import model as M
from ..models.config import reduced as reduce_cfg
from ..runtime import chaos, compile_cache, guard, telemetry
from ..runtime.events import get_logger
from ..runtime.fault import StragglerMonitor, elastic_mesh
from ..train import make_prefill_step, make_serve_step, prebuild_kron_ops
from .scheduler import (
    Request,
    SchedulerConfig,
    new_state,
    poisson_trace,
    step as sched_step,
)


def batch_buckets(max_prefill: int) -> tuple[int, ...]:
    """Prefill BATCH padding buckets: powers of two up to ``max_prefill``
    (plus ``max_prefill`` itself).  A coalesced group of g requests is
    padded to the smallest bucket >= g, so every prefill launch hits one of
    a fixed, prewarmed set of (batch, seq) shapes — variable group sizes
    never cause a re-plan or a re-trace."""
    out = []
    b = 1
    while b < max_prefill:
        out.append(b)
        b *= 2
    out.append(max_prefill)
    return tuple(out)


def _pad_batch(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclasses.dataclass
class ServeReport:
    """What one ``ServeEngine.run`` produced."""

    tokens: dict[int, list[int]]          # rid -> emitted tokens
    metrics: dict[int, dict]              # rid -> wall-clock + step metrics
    steps: int
    duration_s: float
    total_tokens: int
    tokens_per_s: float
    ttft_s: list[float]                   # per finished request
    tpot_s: list[float]                   # per request with >= 2 tokens


class ServeEngine:
    """Executes scheduler actions against the real model.

    The decode batch has a FIXED shape: (max_slots, 1) tokens with a
    per-slot position vector (``model.decode_step`` vector-pos mode).
    Free slots decode garbage that is never read — the fixed shape is what
    keeps the whole serve loop on two compiled executables (one decode,
    one prefill per (batch-bucket, len-bucket) shape) and zero re-plans.
    """

    def __init__(self, cfg, params, scfg: SchedulerConfig, *, max_new: int,
                 temperature: float = 0.0, eos_id: int | None = None,
                 sample_seed: int = 1):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.max_len = max(scfg.buckets) + self.max_new
        self.batch_buckets = batch_buckets(scfg.max_prefill)
        pf = make_prefill_step(cfg, max_len=self.max_len)

        def _pf_slots(params, tokens, true_lens):
            logits, cache = pf(params, tokens)
            # gather each row's last REAL position in-graph: one host
            # transfer of (batch, vocab) instead of per-request eager slices
            rows = logits[jnp.arange(tokens.shape[0]), true_lens - 1]
            return rows, M.cache_to_slots(cache, true_lens=true_lens)

        # everything on the per-request path is jitted — the eager
        # tree_maps in cache_take/cache_put dispatch one op per cache leaf
        # and would otherwise dominate admission cost.  Admission is a
        # single fused move (group-cache row i -> decode slot si), not a
        # take-then-put, so the row never materialises as its own buffers.
        self._prefill = jax.jit(_pf_slots)
        self._decode = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        self._move = jax.jit(
            lambda dst, src, i, si: M.cache_put(dst, M.cache_take(src, i),
                                                si),
            donate_argnums=(0,))
        self._key = jax.random.PRNGKey(sample_seed)
        self.log = get_logger("repro.serve")

    def prewarm(self, mesh=None) -> tuple:
        """Resolve every serving ``KronOp`` plan before the first request:
        one per (batch-bucket, len-bucket) prefill shape plus the decode
        shape (the PR-8 fix — the old single-(batch*prompt) prebuild left
        every other bucket re-planning mid-serve)."""
        shapes = [(bb, lb) for lb in self.scfg.buckets
                  for bb in self.batch_buckets]
        return prebuild_kron_ops(
            self.cfg, prefill_shapes=shapes,
            decode_batch=self.scfg.max_slots, mesh=mesh,
        )

    def compile_shapes(self) -> int:
        """Compile every serving executable up front: one prefill per
        (batch-bucket, len-bucket) shape plus the fixed decode shape.
        Without this the first request to hit a cold shape absorbs an XLA
        compile into its TTFT.  Returns the number of executables built."""
        n = 0
        cache = M.cache_to_slots(
            M.init_cache(self.cfg, self.scfg.max_slots, self.max_len))
        for lb in self.scfg.buckets:
            for bb in self.batch_buckets:
                rows, c = self._prefill(
                    self.params, np.zeros((bb, lb), np.int32),
                    np.ones((bb,), np.int32))
                # admission move: one executable per batch-bucket
                cache = self._move(cache, c, 0, 0)
                jax.block_until_ready(rows)
                n += 1
        jax.block_until_ready(
            self._decode(self.params, cache,
                         jnp.zeros((self.scfg.max_slots, 1), jnp.int32),
                         jnp.zeros((self.scfg.max_slots,), jnp.int32))[0])
        return n + 1

    # -- model calls -------------------------------------------------------

    def _sample(self, lg: np.ndarray, rid: int, index: int) -> int:
        """Next token from one row of host logits.  The key depends only on
        (rid, index) — temperature sampling is per-request deterministic,
        independent of co-batching (the property tests pin this)."""
        lg = lg[: self.cfg.vocab]
        if self.temperature <= 0:
            return int(np.argmax(lg))
        key = jax.random.fold_in(jax.random.fold_in(self._key, rid), index)
        return int(jax.random.categorical(
            key, jnp.asarray(lg) / self.temperature))

    def _prefill_group(self, bucket: int, prompts: list[np.ndarray]):
        """Prefill ``prompts`` padded to ``bucket``; returns per-request
        (first_token_logits_row, batch-1 slot-form cache).

        Guard ladder: rung 0 runs the whole group as ONE (batch-bucket,
        bucket) launch (the fast path; ``serve_admit`` chaos site); rung 1
        degrades to per-request (1, bucket) launches — a capacity failure
        on the grouped shape costs throughput, never a request."""
        g = len(prompts)
        lens = [int(p.shape[0]) for p in prompts]

        def run(tokens: np.ndarray, true_lens: list[int]):
            rows, cache = self._prefill(
                self.params, tokens, np.asarray(true_lens, np.int32))
            return np.asarray(rows), cache

        def rung_bucket():
            chaos.maybe_fail("serve_admit")
            bb = _pad_batch(g, self.batch_buckets)
            tokens = np.zeros((bb, bucket), np.int32)
            for i, p in enumerate(prompts):
                tokens[i, : lens[i]] = p
            rows, cache = run(tokens, lens + [1] * (bb - g))
            return [(rows[i], (cache, i)) for i in range(g)]

        def rung_split():
            out = []
            for p, ln in zip(prompts, lens):
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :ln] = p
                rows, cache = run(tokens, [ln])
                out.append((rows[0], (cache, 0)))
            return out

        return guard.run_ladder(
            f"serve_admit:{bucket}",
            [("bucket", rung_bucket), ("split", rung_split)],
        )

    # -- the serve loop ----------------------------------------------------

    def run(self, requests, *, max_steps: int = 100_000) -> ServeReport:
        """Drive ``requests`` (arrival in scheduler-step units, as from
        ``poisson_trace``) to completion.  Continuous batching: arrivals
        are fed open-loop, prefilled groups are admitted into the live
        decode batch, slots recycle on EOS/max-new."""
        scfg, cfg = self.scfg, self.cfg
        cache = M.cache_to_slots(M.init_cache(cfg, scfg.max_slots,
                                              self.max_len))
        state = new_state(scfg)
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        prompts: dict[int, np.ndarray] = {}
        rng = np.random.RandomState(0)
        for r in pending:
            prompts[r.rid] = rng.randint(
                0, cfg.vocab, size=(r.prompt_len,)).astype(np.int32)

        slot_rid: dict[int, int] = {}            # engine mirror of the slots
        slot_tok = np.zeros((scfg.max_slots, 1), np.int32)
        slot_pos = np.zeros((scfg.max_slots,), np.int32)
        prefilled: dict[int, tuple] = {}   # rid -> (token, (group cache, i))
        tokens: dict[int, list[int]] = {}
        metrics: dict[int, dict] = {}
        eos_next: list[tuple] = []
        mon = StragglerMonitor(action="log")
        n_done, i = 0, 0
        t_start = time.perf_counter()

        while n_done < len(pending) and state.step_idx < max_steps:
            t = state.step_idx
            events = list(eos_next)
            eos_next = []
            while i < len(pending) and int(pending[i].arrival) <= t:
                req = pending[i]
                events.append(("arrive", req))
                metrics[req.rid] = {"arrival_wall": time.perf_counter(),
                                    "arrival_step": t}
                i += 1
            state, actions = sched_step(state, events)
            telemetry.gauge_set("serve.queue_depth", len(state.queued))
            telemetry.observe("serve.queue_depth", float(len(state.queued)))

            for act in actions:
                kind = act[0]
                if kind == "reject":
                    _, rid, reason = act
                    metrics[rid]["reason"] = reason
                    metrics[rid]["finish_wall"] = time.perf_counter()
                    n_done += 1
                    self.log.info(f"reject rid={rid}: {reason}")
                elif kind == "prefill":
                    _, bucket, rids = act
                    with telemetry.span("serve.prefill", bucket=bucket,
                                        group=len(rids)):
                        outs = self._prefill_group(
                            bucket, [prompts[r] for r in rids])
                    now = time.perf_counter()
                    for rid, (lg, row) in zip(rids, outs):
                        tok = self._sample(np.asarray(lg), rid, 0)
                        prefilled[rid] = (tok, row)
                        tokens[rid] = [tok]
                        m = metrics[rid]
                        m["first_token_wall"] = now
                        m["first_token_step"] = t
                        telemetry.observe(
                            "serve.ttft_s", now - m["arrival_wall"])
                        if self.eos_id is not None and tok == self.eos_id:
                            eos_next.append(("eos", rid))
                elif kind == "admit":
                    _, rid, si = act
                    tok, (src, idx) = prefilled.pop(rid)
                    cache = self._move(cache, src, idx, si)
                    slot_rid[si] = rid
                    slot_tok[si, 0] = tok
                    slot_pos[si] = prompts[rid].shape[0]
                    metrics[rid]["admit_step"] = t
                elif kind == "decode":
                    (_, rids) = act
                    mon.start()
                    with telemetry.span("serve.decode_step", batch=len(rids)):
                        logits, cache = self._decode(
                            self.params, cache, slot_tok, slot_pos)
                        lg = np.asarray(logits)[:, -1, :]
                    mon.stop(t)
                    # greedy: ONE vectorized argmax for the whole batch —
                    # per-slot dispatches would dominate the tiny decode step
                    nxt_all = (np.argmax(lg[:, : cfg.vocab], axis=-1)
                               if self.temperature <= 0 else None)
                    for si, rid in list(slot_rid.items()):
                        nxt = (int(nxt_all[si]) if nxt_all is not None
                               else self._sample(lg[si], rid,
                                                 len(tokens[rid])))
                        tokens[rid].append(nxt)
                        slot_tok[si, 0] = nxt
                        slot_pos[si] += 1
                        if self.eos_id is not None and nxt == self.eos_id:
                            eos_next.append(("eos", rid))
                elif kind == "finish":
                    _, rid, reason = act
                    for si, r in list(slot_rid.items()):
                        if r == rid:
                            del slot_rid[si]
                    now = time.perf_counter()
                    m = metrics[rid]
                    m["finish_wall"] = now
                    m["finish_step"] = t
                    m["reason"] = reason
                    n_done += 1
                    telemetry.record_span(
                        "serve.request", m["arrival_wall"],
                        now - m["arrival_wall"], rid=rid, reason=reason,
                        tokens=len(tokens.get(rid, ())),
                    )
            if not actions and not events and i < len(pending):
                # idle gap before the next arrival: fast-forward the clock
                nxt_t = int(pending[i].arrival)
                state = dataclasses.replace(
                    state, step_idx=max(state.step_idx, nxt_t))

        duration = time.perf_counter() - t_start
        total = sum(len(v) for v in tokens.values())
        ttft, tpot = [], []
        for rid, m in metrics.items():
            if "first_token_wall" in m and "finish_wall" in m:
                ttft.append(m["first_token_wall"] - m["arrival_wall"])
                n = len(tokens[rid])
                if n >= 2:
                    tpot.append(
                        (m["finish_wall"] - m["first_token_wall"]) / (n - 1))
        tps = total / max(duration, 1e-9)
        telemetry.gauge_set("serve.tokens_per_s", tps)
        if mon.flagged_steps:
            self.log.info(
                f"stragglers: {len(mon.flagged_steps)} decode step(s) flagged")
        return ServeReport(
            tokens=tokens, metrics=metrics, steps=state.step_idx,
            duration_s=duration, total_tokens=total, tokens_per_s=tps,
            ttft_s=ttft, tpot_s=tpot,
        )


# ---------------------------------------------------------------------------
# Launcher modes
# ---------------------------------------------------------------------------


def _one_shot(args, cfg, log) -> None:
    """Legacy fixed-batch mode (and the fig_serve baseline): prefill one
    batch, decode ``--gen`` tokens, report tokens/s."""
    max_len = args.prompt_len + args.gen
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.prompt_len,
                       batch=args.batch)
    prompts, _ = data.global_batch(0)

    prefill = jax.jit(make_prefill_step(cfg, max_len=max_len))
    step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    t0 = time.time()
    with telemetry.span("prefill", batch=args.batch,
                        prompt_len=args.prompt_len):
        logits, cache = prefill(params, prompts)
        jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    def sample(logits, key):
        lg = logits[:, -1, : cfg.vocab]
        if args.temperature <= 0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / args.temperature).astype(
            jnp.int32
        )

    key = jax.random.PRNGKey(1)
    tok = sample(logits, key)[:, None]
    out_tokens = [tok]
    # Straggler monitor on the decode loop: a persistently slow token
    # step on a serving replica is the same signal as a slow train step
    # on a pod — log it, don't kill the replica.
    mon = StragglerMonitor(action="log")
    t0 = time.time()
    for i in range(args.gen - 1):
        key = jax.random.fold_in(key, i)
        mon.start()
        with telemetry.span("decode_step", step=i):
            logits, cache = step(params, cache, tok,
                                 jnp.int32(args.prompt_len + i))
            tok = sample(logits, key)[:, None]
            jax.block_until_ready(tok)
        mon.stop(i)
        out_tokens.append(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    log.info(f"generated shape: {gen.shape}")
    log.info(f"sample row: {gen[0, :12].tolist()}")
    pre_tps = args.batch * args.prompt_len / max(t_prefill, 1e-9)
    dec_tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    telemetry.gauge_set("prefill.tokens_per_s", pre_tps)
    telemetry.gauge_set("decode.tokens_per_s", dec_tps)
    log.info(f"prefill: {t_prefill:.2f}s ({pre_tps:.0f} tok/s)  "
             f"decode: {t_decode:.2f}s ({dec_tps:.0f} tok/s)")
    if mon.flagged_steps:
        log.info(f"stragglers: {len(mon.flagged_steps)} decode step(s) flagged")


def _pcts(xs: list[float]) -> dict:
    if not xs:
        return {}
    v = sorted(xs)
    at = lambda q: v[min(len(v) - 1, int(q * (len(v) - 1)))]  # noqa: E731
    return {"p50": at(0.5), "p95": at(0.95), "p99": at(0.99)}


def _continuous(args, cfg, mesh, log) -> None:
    """Continuous-batching mode: Poisson open-loop arrivals at
    ``--arrival-rate`` requests per scheduler step."""
    scfg = SchedulerConfig(
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        max_slots=args.slots, max_prefill=args.max_prefill,
        max_wait=args.max_wait,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, scfg, max_new=args.gen,
                         temperature=args.temperature, eos_id=args.eos_id)
    if cfg.kron_ffn:
        for op in engine.prewarm(mesh=mesh if args.distributed else None):
            print(f"kron-ffn {op.describe()}")
    with telemetry.span("serve.compile_shapes"):
        n_exec = engine.compile_shapes()
    log.info(f"compiled {n_exec} serving executables "
             f"({len(scfg.buckets)}x{len(engine.batch_buckets)} prefill "
             f"shapes + decode)")
    reqs = poisson_trace(
        seed=args.seed, rate=args.arrival_rate, n=args.requests,
        prompt_lens=(max(1, args.prompt_len // 4), args.prompt_len),
        max_new=(max(1, args.gen // 4), args.gen),
    )
    rep = engine.run(reqs)
    done = [m for m in rep.metrics.values() if "finish_wall" in m]
    log.info(
        f"served {len(done)}/{args.requests} requests, "
        f"{rep.total_tokens} tokens in {rep.duration_s:.2f}s "
        f"({rep.tokens_per_s:.0f} tok/s, {rep.steps} scheduler steps)")
    log.info(f"ttft_s: {_pcts(rep.ttft_s)}  tpot_s: {_pcts(rep.tpot_s)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--want-model-parallel", type=int, default=16)
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (halves serving memory)")
    ap.add_argument("--kron-ffn", action="store_true",
                    help="Kron-compressed FFN projections: prefill's (B, T, d) "
                         "activations run the batched Kron-Matmul path "
                         "(kron_matmul_batched, shared factors) — one launch "
                         "per projection for the whole serving batch")
    ap.add_argument("--distributed", action="store_true",
                    help="with --kron-ffn: route the batched Kron-FFN prefill "
                         "through kron_matmul_batched_distributed on the "
                         "serving mesh (one collective round per projection "
                         "stage for the whole batch; shapes the mesh cannot "
                         "host fall back to the local batched path)")
    ap.add_argument("--numerics", choices=list(guard.NUMERICS_POLICIES),
                    default=None,
                    help="non-finite guard at StageProgram boundaries "
                         "(default: FASTKRON_NUMERICS or off); serving "
                         "typically wants warn — degraded tokens are better "
                         "than a dead replica")
    ap.add_argument("--telemetry", metavar="OUT.jsonl", default=None,
                    help="KronScope JSONL event sink: spans, guard/chaos "
                         "events, per-round comm metrics, tokens/s gauges")
    ap.add_argument("--trace", metavar="OUT.trace.json", default=None,
                    help="Chrome-trace (Perfetto) export of the host-side "
                         "spans, written at exit")
    # continuous-batching mode (docs/serving.md)
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="requests per scheduler step (Poisson open loop); "
                         "enables continuous batching")
    ap.add_argument("--requests", type=int, default=32,
                    help="number of requests in the arrival trace")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-trace seed (same seed = same trace)")
    ap.add_argument("--buckets", default="16,32,64",
                    help="prompt padding buckets, comma-separated ascending")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots (continuous-batching batch size)")
    ap.add_argument("--max-prefill", type=int, default=4,
                    help="max requests coalesced into one prefill")
    ap.add_argument("--max-wait", type=int, default=8,
                    help="starvation bound: force-schedule a queued request "
                         "after this many scheduler steps")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="token id treated as EOS (default: none; requests "
                         "run to their per-request max-new)")
    args = ap.parse_args()
    compile_cache.configure()
    if args.distributed and not args.kron_ffn:
        ap.error("--distributed requires --kron-ffn (it distributes the "
                 "batched Kron-FFN prefill)")
    if args.numerics is not None:
        guard.set_numerics_policy(args.numerics)
    if args.telemetry or args.trace:
        telemetry.configure(jsonl=args.telemetry, trace=args.trace)
    log = get_logger("repro.serve")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg, dtype="float32")
    if args.kv_quant or args.kron_ffn:
        from dataclasses import replace

        cfg = replace(cfg, kv_quant=args.kv_quant or cfg.kv_quant,
                      kron_ffn=args.kron_ffn or cfg.kron_ffn)
    mesh = elastic_mesh(jax.device_count(), want_model=args.want_model_parallel)

    from ..core.layers import kron_distributed

    dist_scope = (
        kron_distributed(mesh) if args.distributed else contextlib.nullcontext()
    )
    with jax.set_mesh(mesh), dist_scope:
        if args.arrival_rate is not None:
            _continuous(args, cfg, mesh, log)
        else:
            if cfg.kron_ffn:
                # One KronOp per FFN shape, its plan resolved for the serving
                # (batch, prompt-len) rows ONCE before the first trace and
                # reused across every request — the handle-based serving path.
                for op in prebuild_kron_ops(
                    cfg, batch=args.batch, seq_len=args.prompt_len,
                    mesh=mesh if args.distributed else None,
                ):
                    print(f"kron-ffn {op.describe()}")
            _one_shot(args, cfg, log)
    # ONE merged exit report: guard health carries the telemetry snapshot
    # (counters, gauges, histogram percentiles) when KronScope is live.
    report = guard.health_report()
    if telemetry.active() or report["events"] or any(
        h["degraded_calls"] or h["errors"] for h in report["ops"].values()
    ):
        log.info(f"health: {report}")
    telemetry.shutdown()


if __name__ == "__main__":
    main()
