"""The benchmark's own tests run on the CPU against the program's jnp
reference path (``REPRO_PALLAS=ref``), at sizes a test run can hold."""
import os
import sys

os.environ.setdefault("REPRO_PALLAS", "ref")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
