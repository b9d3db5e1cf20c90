"""The benchmark's own tests run on the CPU backend (``python -m pytest
benchmark/tests -q``); they are no part of the repo's tier-1 tests."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
