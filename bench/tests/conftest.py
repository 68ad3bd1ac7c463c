"""The benchmark's CPU tests: its modules and the program are importable,
and JAX stays on the CPU (the TPU library is never loaded)."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
