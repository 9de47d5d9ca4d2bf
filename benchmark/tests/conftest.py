"""The benchmark's self-tests run on the CPU; they are not tier-1 tests.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
