"""The benchmark's own tests run on the CPU: ``python -m pytest
benchmark/tests -q``.  The steering (platform, tiny sizes) lives here and
in the tests, not in an option of ``run.py``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
