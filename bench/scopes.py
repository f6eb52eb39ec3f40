#!/usr/bin/env python3
"""Device time by the layer that issued it: the program's KronScope scopes.

The program names its layers with ``telemetry.scope`` (``stage``, ``op``,
``round``, ``model.ffn``, ``optim.update``, ...).  Each compiled
instruction's ``op_name`` metadata holds the chain of scopes it was traced
in, and the trace reduction (``bench/trace.py``) holds each chip's self time
per instruction.  Joined on the instruction's name, they give self time by
scope chain and by class: kernel (the emitter's), collective, or other.
Self time goes to the op's whole chain, so a metric can read a scope alone
(its innermost scope: ``stage`` without the ``op`` around it) or with what
nests inside it (``model.ffn`` with its Kron stages).

Run as a script it makes one traced run of a cell through the harness,
unchanged, and prints the harness's result line with a ``scopes`` entry
added (the table, and what each metric of ``METRICS`` reads):

    python3 bench/scopes.py --workload gp26-fwd --seed 1 --seconds 3 [--keep DIR]

``--keep DIR`` also writes the traced window (``<workload>.xplane.pb``) and
the text of the program that ran, source locations stripped
(``<workload>.hlo.txt``), into DIR.
"""
from __future__ import annotations

import collections
import re

CLASSES = ("kernel", "collective", "other")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"kronscope\.([^/()]+)")
_REF = re.compile(r"%([^\s,(){}]+)")
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=(%[^\s,}]+)|branch_computations=\{([^}]*)\}")


def _chain(op_name: str) -> str:
    """The scopes of one ``op_name``, outermost first, each once."""
    names = _SCOPE.findall(op_name.split(";", 1)[0])
    return "/".join(dict.fromkeys(names))


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Each instruction of a compiled program (``compiled.as_text()``) and
    the chain of KronScope scopes in its ``op_name``, outermost first and
    joined by ``/`` (``"model.ffn/op/program/stage"``); ``""`` where it
    names none.  A scope keeps its name under a transformation
    (``transpose(jvp(kronscope.stage))`` is ``stage``) and counts once in a
    chain; where XLA merged the names of fused operations (``a/b;b/c``),
    the first, whole one counts.

    An instruction that XLA made has no ``op_name`` at all (a copy that
    layout assignment added, a loop that moves a large array in pieces): it
    takes the chain of the first of its operands that has one, the value it
    copies or unpacks, else that of the instruction that calls its
    computation."""
    chains, operands, computation, caller = {}, {}, {}, {}
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                current = c.group(1)
            continue
        name = m.group(1)
        computation[name] = current
        op_name = _OP_NAME.search(line)
        if op_name is not None:
            chains[name] = _chain(op_name.group(1))
        else:
            operands[name] = _REF.findall(line[m.end():].split("), ", 1)[0])
        for one, branches in _CALLED.findall(line):
            for comp in _REF.findall(one or branches):
                caller.setdefault(comp, name)

    resolved: dict[str, str] = {}

    def resolve(name: str, seen: frozenset) -> str:
        if name in chains:
            return chains[name]
        if name in resolved:
            return resolved[name]
        if name in seen or name not in operands:
            return ""
        seen = seen | {name}
        found = next((c for c in (resolve(o, seen) for o in operands[name]) if c), "")
        if not found and computation.get(name) in caller:
            found = resolve(caller[computation[name]], seen)
        resolved[name] = found
        return found

    return {name: resolve(name, frozenset()) for name in computation}


def innermost(chain: str) -> str:
    """The innermost scope of a chain (``""``: none)."""
    return chain.rsplit("/", 1)[-1]


def table(summary, scopes: dict, kernel_names) -> list[collections.Counter]:
    """Per chip of the reduced window ``summary``: seconds of self time by
    (scope chain, class).  An operation the program's text does not name
    has no scope.  Each chip's entries sum to its operations' self time."""
    from bench.trace import is_collective, is_kernel

    out = []
    for d in summary.devices:
        t = collections.Counter()
        for op, s in d.ops.items():
            cls = ("collective" if is_collective(op)
                   else "kernel" if is_kernel(op, kernel_names) else "other")
            t[(scopes.get(op, ""), cls)] += s
        out.append(t)
    return out


def _in(chain: str, names) -> bool:
    return any(n in chain.split("/") for n in names)


def _sum(tables, where, classes) -> float:
    """Self time, mean over chips, of the (chain, class) entries kept."""
    return sum(v for t in tables for (chain, cls), v in t.items()
               if cls in classes and where(chain)) / len(tables)


def scope_s(tables, scopes: dict, names, alone: bool, classes=CLASSES) -> float | None:
    """Self time, mean over chips, of the operations of a class in
    ``classes`` issued in one of the scopes ``names``: with ``alone``, where
    that scope is the innermost (not in the scopes nested in it), else
    anywhere in the chain.  None when no instruction of the program is in
    any of those scopes: the layer is not in the program, which is not the
    same as taking no time."""
    if not any(_in(c, names) for c in set(scopes.values())):
        return None
    where = (lambda c: innermost(c) in names) if alone else (lambda c: _in(c, names))
    return _sum(tables, where, classes)


def unscoped_s(tables, scopes: dict) -> float | None:
    """Self time, mean over chips, of the operations in no scope; None when
    the program's text names no scope at all (an executable that a tree
    without scopes compiled, or a compile-cache entry it wrote)."""
    if not any(scopes.values()):
        return None
    return _sum(tables, lambda c: c == "", CLASSES)


# The per-layer metrics a traced run reads from the scopes, per call or step
# in ms: (scopes, whether alone, classes).  ``device.unscoped_pct`` is
# apart: a share of busy time.
METRICS = {
    "emit.stage_glue_ms": (("stage", "stage_grad"), True, ("other",)),
    "engine.op_glue_ms": (("op", "op_bwd", "program"), True, ("other",)),
    "mesh.round_glue_ms": (("round", "round_bwd"), True, ("other",)),
    "model.attn_ms": (("model.attn",), False, CLASSES),
    "model.ffn_ms": (("model.ffn",), False, CLASSES),
    "model.head_ms": (("model.head",), False, CLASSES),
    "optim.update_ms": (("optim.update",), False, CLASSES),
}


def read(summary, hlo_text: str, calls: int, kernel_names) -> dict:
    """What each metric of ``METRICS`` reads, and ``device.unscoped_pct``
    (unscoped self time over busy time), for a window of ``calls`` calls;
    a metric whose scope is not in the program is left out, and nothing is
    read from a program whose text names no scope."""
    scopes = op_scopes(hlo_text)
    tables = table(summary, scopes, kernel_names)
    out = {}
    for name, (names, alone, classes) in METRICS.items():
        s = scope_s(tables, scopes, names, alone, classes)
        if s is not None:
            out[name] = s / calls * 1e3
    unscoped = unscoped_s(tables, scopes)
    if unscoped is not None and summary.busy_s > 0:
        out["device.unscoped_pct"] = 100.0 * unscoped / summary.busy_s
    return out


def breakdown(summary, hlo_text: str, kernel_names) -> list:
    """``[chain:class, seconds]`` over the window, mean over chips, largest
    first; ``(none)`` is the unscoped share."""
    tables = table(summary, op_scopes(hlo_text), kernel_names)
    mean = collections.Counter()
    for t in tables:
        for (chain, cls), v in t.items():
            mean[f"{chain or '(none)'}:{cls}"] += v / len(tables)
    return [[k, v] for k, v in mean.most_common()]


def top_ops(summary, hlo_text: str, n: int = 20) -> list:
    """The ``n`` operations of most self time, mean over chips, each as
    ``[op, scope chain, seconds]``."""
    scopes = op_scopes(hlo_text)
    mean = collections.Counter()
    for d in summary.devices:
        for op, s in d.ops.items():
            mean[op] += s / len(summary.devices)
    return [[op, scopes.get(op, ""), s] for op, s in mean.most_common(n)]


def main(argv=None, *, t0: float, **run_kw) -> int:
    """One traced run of a cell through ``harness.run`` (``run_kw`` passed
    on), with the program's text and the reduced window kept for the scope
    view."""
    import argparse
    import json
    import pathlib
    import shutil
    import sys

    from bench import harness
    from bench import trace as trace_mod

    ap = argparse.ArgumentParser(prog="bench/scopes.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=pathlib.Path, default=None)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    kept = {}
    load_driver, reduce_dir = harness.load_driver, trace_mod.reduce_dir

    def keeping_driver(kind):
        class Kept(load_driver(kind)):
            def check(self):  # the program is freed here: keep its text first
                kept["hlo"], kept["kernels"] = self.fn.as_text(), self.kernel_names()
                return super().check()
        return Kept

    def keeping_reduce(trace_dir, kernel_names=()):
        if args.keep is not None:
            args.keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(trace_mod.find_xplane(trace_dir),
                        args.keep / f"{args.workload}.xplane.pb")
        kept["summary"] = reduce_dir(trace_dir, kernel_names)
        return kept["summary"]

    harness.load_driver, trace_mod.reduce_dir = keeping_driver, keeping_reduce
    try:
        out = harness.run(args.workload, args.seed, args.seconds, True, t0=t0, log=log,
                          **run_kw)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        harness.load_driver, trace_mod.reduce_dir = load_driver, reduce_dir
    summary, hlo = kept["summary"], kept["hlo"]
    if args.keep is not None:
        from repro.runtime.hlo_analysis import strip_source_locations

        (args.keep / f"{args.workload}.hlo.txt").write_text(strip_source_locations(hlo) + "\n")
    out["scopes"] = {"metrics": read(summary, hlo, out["attempted"], kept["kernels"]),
                     "table": breakdown(summary, hlo, kept["kernels"]),
                     "top_ops": top_ops(summary, hlo),
                     "busy_s": summary.busy_s}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import pathlib
    import sys
    import time

    T0 = time.perf_counter()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main(t0=T0))
