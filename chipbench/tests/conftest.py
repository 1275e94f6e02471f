"""The benchmark's own tests run on the CPU and import its modules from
the benchmark directory and the program from ``src/``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
BENCH_DIR = HERE.parent
REPO = BENCH_DIR.parent
for p in (BENCH_DIR, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
