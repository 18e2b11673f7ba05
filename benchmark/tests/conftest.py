"""The harness's own tests: run by hand, on the CPU, never part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for path in (str(BENCH.parent), str(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)
