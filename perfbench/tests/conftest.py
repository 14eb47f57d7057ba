"""Tests of the benchmark itself: run with
`JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q -p no:cacheprovider`.
They are not part of the repo's tier-1 suite (tests/)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
