"""The benchmark's own tests: CPU only, `python -m pytest benchmarks/tests -q`.

They hold the yardstick itself: the trace reducer on a recorded trace, the
MAC count, the plain reference against the program at the tiny presets, the
harness's discovery by name, and the command's behaviour off the chip.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
