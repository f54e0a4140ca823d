"""These tests belong to the benchmark, not to the repo's tier-1 suite
(``tests/``): run them with ``python -m pytest benchmarks/tests -q``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]
