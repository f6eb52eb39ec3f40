"""Reduce a profiler trace of the measured window to what the per-layer
metrics read.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` that
``jax.profiler.start_trace`` wrote.  Each chip is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation run,
with start and duration in nanoseconds on the host's clock.  The host's
planes hold the benchmark's own spans: ``bench.window`` around the measured
window and ``bench.call`` around each call.

Per chip, within the window:

* busy: the union of the operations' intervals; idle is the rest;
* kernel time: the operations named for one of the emitter's kernels;
* collective time: the operations that are collectives, and the part of
  their union during which no other operation runs (exposed);
* other time: every other operation (the engine's transposes, pads and
  copies, and stages XLA runs).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "send", "recv")
TOP = 10
MIN_GAP_NS = 1000  # shorter gaps between operations are the trace's rounding


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def op_name(event_name: str) -> str:
    """The HLO instruction's name: ``%fusion.3 = f32[...] fusion(...)`` ->
    ``fusion.3``.  Operands are not names: a reshape of a kernel's output
    names the kernel among its operands."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def is_collective(op: str) -> bool:
    """A TPU trace names a collective ``all_to_all.24`` as often as
    ``all-to-all-start.3``."""
    return op.lower().replace("_", "-").startswith(COLLECTIVES)


def is_kernel(op: str, kernel_names) -> bool:
    return op.startswith(tuple(kernel_names))


def self_times(events) -> list[float]:
    """Each event's duration less the events nested inside it (a ``while``
    holds its body's operations), for (start, end) in any order."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e in events]
    stack: list[int] = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


@dataclasses.dataclass
class DeviceStats:
    name: str
    busy_s: float
    kernel_s: float
    collective_s: float
    exposed_collective_s: float
    other_s: float
    ops: dict  # op name -> seconds
    busy: list  # disjoint busy intervals, ns


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: list
    gaps: list  # [(what the host was doing, seconds)], longest first

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def mean(self, field: str) -> float:
        return sum(getattr(d, field) for d in self.devices) / len(self.devices)

    def breakdown(self) -> dict:
        ops = collections.Counter()
        for d in self.devices:
            for k, v in d.ops.items():
                ops[k] += v / len(self.devices)
        return {"device_ops": [[k, v] for k, v in ops.most_common(TOP)],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def host_spans(pd) -> list[tuple[str, float, float]]:
    """Every event on the host's planes: (name, start ns, end ns)."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _attribute(gap, spans) -> str:
    """What the host was doing in an idle gap: the most specific of its spans
    (the shortest) that covers most of the gap."""
    s, e = gap
    best, best_key = "no host span", None
    for name, hs, he in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(e, he) - max(s, hs)
        if cover <= 0.5 * (e - s):
            continue
        key = (he - hs)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def reduce(pd, kernel_names=(), window=None) -> Summary:
    """The summary of the loaded trace ``pd`` within ``window`` (start and
    end in ns; by default the ``bench.window`` span's)."""
    spans = host_spans(pd)
    if window is None:
        windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        window = windows[0]
    w0, w1 = window
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX) or not plane.name[len(DEVICE_PREFIX):].isdigit():
            continue
        lines = [l for l in plane.lines if l.name == OPS_LINE]
        if not lines:
            continue
        named, every = [], []
        for ev in lines[0].events:
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e > s:
                named.append(op_name(ev.name))
                every.append((s, e))
        ops = collections.Counter()
        for op, own in zip(named, self_times(every)):
            ops[op] += own * 1e-9
        coll = [iv for op, iv in zip(named, every) if is_collective(op)]
        compute = [iv for op, iv in zip(named, every) if not is_collective(op)]
        kernels = [iv for op, iv in zip(named, every) if is_kernel(op, kernel_names)]
        busy = union(every)
        compute_s = length(union(compute))
        kernel_s = length(union(kernels))
        devices.append(DeviceStats(
            name=plane.name, busy_s=length(busy) * 1e-9, kernel_s=kernel_s * 1e-9,
            collective_s=length(union(coll)) * 1e-9,
            exposed_collective_s=(length(busy) - compute_s) * 1e-9,
            other_s=(compute_s - kernel_s) * 1e-9, ops=dict(ops), busy=busy))
    if not devices:
        raise ValueError("no device plane with XLA operations in the trace")
    busy = devices[0].busy
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] - edges[i] >= MIN_GAP_NS]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_attribute(g, spans), (g[1] - g[0]) * 1e-9) for g in gaps[:TOP]]
    return Summary(window_s=(w1 - w0) * 1e-9, devices=devices, gaps=named)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def reduce_file(path: str, kernel_names=()) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), kernel_names)


def reduce_dir(trace_dir: str, kernel_names=()) -> Summary:
    return reduce_file(find_xplane(trace_dir), kernel_names)
