"""The benchmark's own tests: ``python -m pytest bench/tests``.

They run on the CPU at tiny sizes; the repository's tier-1 suite does not
collect them (``testpaths = tests``)."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
