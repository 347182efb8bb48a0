"""Tests of the benchmark's own yardstick. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the repository's tier-1 suite (tests/).
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
