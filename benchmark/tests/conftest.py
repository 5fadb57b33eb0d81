"""Tests of the benchmark's own code; they run on the CPU with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

and load no TPU library at import."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(pathlib.Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
