"""The benchmark's own tests: the yardstick checked against the program at toy
size on the CPU.  Run with ``python -m pytest benchmarks/tests``; the repo's
tier-1 command collects ``tests/`` only and does not come here."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
