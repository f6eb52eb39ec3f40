#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, makes its inputs and weights
from the seed on the device, compiles and warms up (the set-up), measures
for ``--seconds``, compares what the timed calls produced with the plain
reference, and prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a profiler trace of the
window), ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``
(each number compared, with its limit).  It refuses to run without a TPU.
"""
import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
