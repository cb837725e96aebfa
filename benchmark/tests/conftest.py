"""The benchmark's own tests: run with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 suite. Everything runs on the CPU
at toy sizes; nothing here is a measurement."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
