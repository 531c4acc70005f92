"""The benchmark's own tests: the harness at tiny sizes on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They are run by path; the repository's default test run does not collect
them.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
