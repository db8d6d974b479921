"""Where the benchmark lives, for its tests (no JAX import here)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
