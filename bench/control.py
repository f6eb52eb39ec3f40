#!/usr/bin/env python3
"""The readings a cell's limits are set from, taken on the chip in one
process; the benchmark's own runs never run this.

    python3 bench/control.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault half_batch] [--out FILE]

For each of ``--seeds``: a sound run's numbers, as ``run.py`` compares them
after one timed call (or a training cell's first steps), with no window.
For each of ``--control-seeds``: the control's, the reference computed one
precision step below the configuration's (``reference/numerics.py``) put in
the program's place.  ``--fault half_batch`` (training cells) reads the
reference on half of each batch in the program's place.  Each reading is
one JSON line on standard output and in ``--out``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

CONTROL_MODE = {"kron": "high", "lm": "fp8"}


def readings(workload: str, seeds, control_seeds=(), fault: str | None = None, *,
             root=harness.ROOT, require_tpu: bool = True, log=print):
    """Yield one dict per reading: ``{"seed", "what", "checks"}``."""
    cell = harness.resolve(workload, root)
    harness.configure_jax()
    devs = harness.devices_for(cell, require_tpu)[: cell.chips]
    Driver = harness.load_driver(cell.config["kind"])
    for seed in seeds:
        d = Driver(cell, seed, devs, log=log)
        d.setup(seconds=1.0)
        d.call(0)
        yield {"seed": seed, "what": "program", "checks": d.check()}
        del d
        gc.collect()
    mode = CONTROL_MODE[cell.config["kind"]]
    for seed in control_seeds:
        d = Driver(cell, seed, devs, log=log)
        if cell.config["kind"] == "kron":
            d.make_inputs()
            checks = d.control(mode)
        elif fault == "half_batch":
            checks = d.control("highest", rows=d.batch // 2)
        else:
            checks = d.control(mode)
        yield {"seed": seed, "what": fault or f"control:{mode}", "checks": checks}
        del d
        gc.collect()


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=("half_batch",))
    ap.add_argument("--out")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    out = open(args.out, "a") if args.out else None
    try:
        for r in readings(args.workload, ints(args.seeds), ints(args.control_seeds),
                          args.fault, log=log):
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
