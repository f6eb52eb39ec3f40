#!/usr/bin/env python3
"""The readings an expert-layer training cell's limits are set from, taken
on the chip in one process; the benchmark's own runs never run this.

    python3 bench/moe_control.py --workload dsv2lite-train --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault half_batch|unchanged|renorm_topk ...] [--out FILE]

As ``bench/control.py`` for a configuration of kind ``moe_lm``: for each of
``--seeds`` a sound run's numbers, for each of ``--control-seeds`` the fp8
control's, or with ``--fault`` (repeatable: each in turn, in one process) a
broken program's:

* ``half_batch``: the reference on half of each step's tokens (half the
  rows, or with one row the first half of its positions) in the program's
  place;
* ``unchanged``: the program's own step with its state update dropped;
* ``renorm_topk``: the reference with the top-k weights renormalised to sum
  1 in the program's place, so that the check is shown to see the routing.

One JSON line per reading on standard output and in ``--out``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import control, harness  # noqa: E402

KIND = "moe_lm"
CONTROL_MODE = "fp8"
FAULTS = ("half_batch", "unchanged", "renorm_topk")


def _unchanged_step(make):
    """``make_train_step`` whose steps return the state they were given."""

    def factory(cfg, opt_cfg, **kw):
        step = make(cfg, opt_cfg, **kw)
        return lambda state, batch: (state, step(state, batch)[1])

    return factory


def _fault(d, fault: str) -> dict:
    if fault == "half_batch":
        if d.batch > 1:
            return d._judge(d._reference("highest", rows=d.batch // 2),
                            d._reference("highest"))
        return d._judge(d._reference("highest", seq=d.seq // 2), d._reference("highest"))
    if fault == "renorm_topk":
        return d._judge(d._reference("highest", norm_topk_prob=True),
                        d._reference("highest"))
    import repro.train

    make = repro.train.make_train_step
    repro.train.make_train_step = _unchanged_step(make)
    try:
        d.setup(seconds=1.0)
        d.call(0)
    finally:
        repro.train.make_train_step = make
    return d.check()


def readings(workload: str, seeds, control_seeds=(), fault: str | None = None, *,
             root=harness.ROOT, require_tpu: bool = True, log=print):
    """Yield one dict per reading: ``{"seed", "what", "checks"}``."""
    cell = harness.resolve(workload, root)
    if cell.config["kind"] != KIND:
        raise harness.BenchError(f"{workload} is of kind {cell.config['kind']!r}, not {KIND!r}")
    control.CONTROL_MODE.setdefault(KIND, CONTROL_MODE)
    yield from control.readings(workload, seeds, () if fault else control_seeds, root=root,
                                require_tpu=require_tpu, log=log)
    if not fault:
        return
    harness.configure_jax()
    devs = harness.devices_for(cell, require_tpu)[: cell.chips]
    Driver = harness.load_driver(KIND)
    for seed in control_seeds:
        d = Driver(cell, seed, devs, log=log)
        yield {"seed": seed, "what": fault, "checks": _fault(d, fault)}
        del d
        gc.collect()


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=FAULTS, action="append")
    ap.add_argument("--out")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    out = open(args.out, "a") if args.out else None
    try:
        for i, fault in enumerate(args.fault or [None]):
            for r in readings(args.workload, ints(args.seeds) if i == 0 else [],
                              ints(args.control_seeds), fault, log=log):
                r["t"] = time.time()
                line = json.dumps({"workload": args.workload, **r})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
