"""The benchmark's tests import ``benchmarks.*`` from the repository root
and ``benchmark_tiny`` from this directory, wherever pytest was started."""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(os.path.dirname(_HERE)), _HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)
