"""The benchmark's spine: find a cell's files by name, set up, measure a
window, check the answers, and print the result line.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix.  Everything particular to one of them is a file of its own,
found by name:

* ``configs/<config>.json``: the sizes; its ``kind`` names the driver in
  ``kinds/`` and the plain reference in ``reference/`` that it is run with;
* ``traffic/<traffic>.json``: the parameters of the loop (the call, batch,
  sequence, optimizer), read by the driver;
* ``limits/<workload>.json``: the limit of every number compared;
* ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` of one
  metric, from the window's counts or from the reduced trace; a metric
  split by what it moves (``emit.kernel_ms.kron``, ``emit.kernel_ms.train``)
  shares its quantity's reader (``emit.kernel_ms.py``).

So a later cell, configuration or metric is a new file and an entry in
``BENCHMARK.json``, and no file here changes.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
# Calls queued ahead hold their outputs: they may take at most this share
# of the memory a chip has left beside what warm-up peaked at and the
# program's temporaries.
QUEUE_MEMORY_SHARE = 0.8


class BenchError(Exception):
    """A cell that cannot run as named: a missing file, chip or key."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    bench_dir: pathlib.Path


def _json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def resolve(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    spec = _json(root / "BENCHMARK.json")
    w = _by_name(spec["workloads"], workload, "workload")
    c = _by_name(spec["configs"], w["config"], "configuration")
    bench_dir = root / pathlib.Path(c["file"]).parts[0]
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload] if m["moves"] in moved else [])]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=c["name"], config=_json(root / c["file"]),
        traffic_name=w["traffic"],
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench_dir / "limits" / f"{workload}.json"),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir,
    )


def load_reader(bench_dir: pathlib.Path, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``.  A metric split by
    the end-to-end metric it moves (``device.idle_pct.train``) is read by
    its quantity's reader (``device.idle_pct.py``) unless it has its own."""
    names = [metric]
    while "." in names[-1]:
        names.append(names[-1].rsplit(".", 1)[0])
    paths = [bench_dir / "metrics" / f"{n}.py" for n in names]
    path = next((p for p in paths if p.exists()), None)
    if path is None:
        raise BenchError(f"no reader for metric {metric!r} at any of {[str(p) for p in paths]}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(kind: str):
    """The driver class of a configuration's ``kind`` (``kinds/<kind>.py``)."""
    try:
        return importlib.import_module(f"bench.kinds.{kind}").Driver
    except ModuleNotFoundError as e:
        raise BenchError(f"no driver for configuration kind {kind!r}: {e}") from None


def seed_key(seed: int):
    """A JAX key for any whole number up to 2**63: the low 31 bits seed it,
    the rest is folded in."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise BenchError(f"--seed must be a whole number >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    setup_s: float
    window_s: float
    calls: int
    work: dict  # per call and chip: flops, bytes; per call: tokens
    peak: dict
    chips: int
    trace: Any = None  # bench.trace.Summary of the traced window


def _peak_memory(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def per_chip_bytes(tree) -> int:
    """Bytes of the arrays in ``tree`` on the chip that holds most of them."""
    import jax

    per = collections.Counter()
    for a in jax.tree.leaves(tree):
        for shard in a.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return max(per.values(), default=0)


def queue_depth(devices, call_s: float, ahead_s: float, out_bytes: int = 0,
                reserve: int = 0, temp_bytes: int = 0) -> int:
    """How many calls the window keeps queued beyond the one it waits on.

    ``ahead_s`` seconds of calls, so that the chip stays fed while the host
    stands still; but each queued call holds its own outputs (``out_bytes``
    a chip).  They, and ``reserve`` bytes of answers kept for the check,
    have to fit into ``QUEUE_MEMORY_SHARE`` of what the fullest chip has
    left beside what warm-up peaked at and the program's ``temp_bytes`` of
    temporaries, which the device's peak does not count.  A backend that
    reports no memory keeps one call queued."""
    if ahead_s <= 0:
        return 0
    depth = math.ceil(ahead_s / max(call_s, 1e-6))
    if not out_bytes:
        return depth
    stats = [d.memory_stats() or {} for d in devices]
    limits = [s.get("bytes_limit") for s in stats]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    if None in limits or None in peaks:
        return min(depth, 1)
    free = min(limits) - max(peaks) - temp_bytes
    room = QUEUE_MEMORY_SHARE * free - reserve
    return min(depth, max(0, int(room // out_bytes)))


def _health_failures() -> int:
    """Calls the program's guard degraded, and fallbacks it recorded."""
    from repro.runtime import guard

    report = guard.health_report()
    degraded = sum(int(h["degraded_calls"]) + sum(h["errors"].values())
                   for h in report["ops"].values())
    return degraded + sum(int(n) for n in report["events"].values())


def configure_jax():
    """Keep the compile cache at its fixed place in the checkout, for every
    program however quick to compile, so that every run after a cell's
    first is served from it."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        raise BenchError(f"the program (src/repro) is not in this checkout: {e}") from None
    cache = compile_cache.configure()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def devices_for(cell: Cell, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}; refusing to run")
    if len(devs) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips, JAX found {len(devs)}")
    return devs


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, root: pathlib.Path = ROOT, require_tpu: bool = True,
        log=print) -> dict:
    """One run of a cell: the result as a dict, checks last."""
    cell = resolve(workload, root)
    configure_jax()
    import jax

    from bench import work

    devs = devices_for(cell, require_tpu)
    kind = devs[0].device_kind
    peak = work.peaks(kind) if require_tpu else {"bf16_flops_per_s": 1.0,
                                                 "hbm_bytes_per_s": 1.0}
    driver = load_driver(cell.config["kind"])(cell, seed, devs[: cell.chips], log=log)
    driver.setup(seconds)
    log(f"[bench] {cell.name}: {driver.ahead} calls kept queued ahead in the window")
    failed0 = _health_failures()
    readers = {m["name"]: load_reader(cell.bench_dir, m["name"])
               for m in (cell.per_layer if trace else cell.end_to_end)}

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t0
    # Each call is dispatched and the oldest queued one waited on, so that
    # ``driver.ahead`` calls stay queued; when the time is up nothing more
    # is sent, everything sent is waited for, and only then is the clock
    # read: every call counts, over all of that time.
    pending = collections.deque()
    calls, start = 0, time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            with jax.profiler.TraceAnnotation(CALL_SPAN):
                pending.append(driver.call(calls))
                if len(pending) > driver.ahead:
                    jax.block_until_ready(pending.popleft())
            calls += 1
            if time.perf_counter() - start >= seconds:
                break
        jax.block_until_ready(list(pending))
        pending.clear()
    window_s = time.perf_counter() - start
    summary = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_mod

        try:
            summary = trace_mod.reduce_dir(trace_dir, kernel_names=driver.kernel_names())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    failed = _health_failures() - failed0
    memory = _peak_memory(devs[: cell.chips])

    r = Run(setup_s=setup_s, window_s=window_s, calls=calls, work=driver.work(),
            peak=peak, chips=cell.chips, trace=summary)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    for name, read in readers.items():
        v = read(r)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}

    checks = driver.check()
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": calls, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    return out


def main(argv=None, *, t0: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="bench/run.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace), t0=t0, log=log)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']:.6g} (limit {c['limit']:.6g})")
    print(json.dumps(out), flush=True)
    return 0
