"""CPU self-tests of the benchmark (not part of the repo's tests/):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import kubernetes_tpu  # noqa: E402,F401  (x64 before any array)
