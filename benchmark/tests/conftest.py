"""Tests of the benchmark's own yardstick.  They run on the CPU and never
need the chip; ``python -m pytest benchmark/tests -q`` from the root."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
