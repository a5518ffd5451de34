"""The benchmark's own tests. Run by hand, from the root of the repo:

    python3 -m pytest benchmark/tests -q

They are not part of the repository's tier-1 suite (``tests/``). The
test process itself never touches JAX's devices: the cells run as
subprocesses, and the pure arithmetic needs none.
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
