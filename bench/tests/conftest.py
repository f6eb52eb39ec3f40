"""Tests of the benchmark itself, on the CPU at tiny sizes:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
