"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig9,tab5]

Prints one CSV row per measurement (name,key=value,...).  CPU container:
absolute GFLOP/s are not paper-comparable; the reproduced claims are the
RATIOS (FastKron vs shuffle vs FTMMT) and the HLO-derived bytes / comm
volumes, which are hardware-independent.  Roofline/§Perf numbers come from
launch/dryrun.py, not from here.  Under a TPU backend the host-mesh
rehearsals (``HOST_MESH_REHEARSALS``) are refused, not run.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

ALL = ["fig9", "fig_bwd", "fig_batched", "fig_dist_batched",
       "fig_dist_overlap", "fig_serve", "fig_optim", "tab1", "tab2", "tab3",
       "fig10", "fig11", "tab5"]

# Host-mesh rehearsals: each runs its mesh in a child process on virtual CPU
# devices.  Under a TPU backend this process holds the chip, so the child
# could only time the host CPU; those numbers are never reported as chip
# results.
HOST_MESH_REHEARSALS = {"fig11", "fig_dist_batched", "fig_dist_overlap"}


def refused_on_tpu(names) -> list[str]:
    """The requested host-mesh rehearsals, when JAX's backend is a TPU."""
    rehearsals = [n for n in names if n in HOST_MESH_REHEARSALS]
    if not rehearsals:
        return []
    import jax

    return rehearsals if jax.default_backend() == "tpu" else []


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(ALL))
    ap.add_argument("--telemetry", metavar="OUT.jsonl", default=None,
                    help="KronScope JSONL event sink for the whole run")
    ap.add_argument("--trace", metavar="OUT.trace.json", default=None,
                    help="Chrome-trace export of host-side spans at exit")
    args = ap.parse_args()
    if args.telemetry or args.trace:
        from repro.runtime import telemetry

        telemetry.configure(jsonl=args.telemetry, trace=args.trace)
    names = args.only.split(",") if args.only else ALL
    failures = []
    for name in refused_on_tpu(names):
        failures.append(name)
        print(f"# {name} REFUSED: a host-mesh rehearsal (its mesh runs on "
              f"virtual CPU devices in a child process); on a TPU backend it "
              f"would report CPU timings from the chip machine", flush=True)
    for name in names:
        if name in failures:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            for row in mod.run(quick=args.quick):
                print(row, flush=True)
            print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
        except Exception as e:
            failures.append(name)
            traceback.print_exc()
            print(f"# {name} FAILED: {e}", flush=True)
    if args.telemetry or args.trace:
        from repro.runtime import telemetry

        telemetry.shutdown()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# ALL BENCHMARKS OK")


if __name__ == "__main__":
    main()
