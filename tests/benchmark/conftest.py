"""The benchmark's own tests. They import the benchmark by the same two
roots ``benchmarks/run.py`` puts on the path."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "benchmarks")):
    if path not in sys.path:
        sys.path.insert(0, path)
