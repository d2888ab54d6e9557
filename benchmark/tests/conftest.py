"""Tests of the benchmark's own arithmetic.  Run from the repo root:
`python -m pytest benchmark/tests -q`.  None imports jax."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
