"""Tests of the benchmark's own code.  Run by hand:

    python -m pytest benchmark/tests -q

Tier-1 (`pytest tests/`) does not collect this directory.  Everything runs on
the CPU; nothing here is a device number.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCHMARK)
for path in (ROOT, BENCHMARK):
    if path not in sys.path:
        sys.path.insert(0, path)
