"""The benchmark's own tests run on the CPU at a toy size:

    python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of tier-1 (``tests/``); they guard the yardstick."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
