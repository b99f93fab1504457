"""CPU tests of the benchmark's own code, run by hand:

  python -m pytest bench/tests

They are outside the repository's ``testpaths``.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
