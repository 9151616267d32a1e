"""The benchmark's own tests run on the CPU (`JAX_PLATFORMS=cpu`, the
operator's choice in JAX's terms, which a node with the tpu backend
accepts): `python -m pytest benchmark/tests -q`. They are not part of
the repository's tier-1 run."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
