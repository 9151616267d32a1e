"""Test configuration.

The suite runs on the CPU: `JAX_PLATFORMS=cpu` is set before JAX is
imported (the operator's choice in JAX's own terms — a node with
SIGNATURE_VERIFY_BACKEND = "tpu" accepts it, see
main/application.py `device_backend_refusal`), and multi-chip sharding
is tested on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count), mirroring how the driver
dry-runs the multi-chip path. XLA_FLAGS is read lazily at backend
init, so setting it here (before any jax op runs) still takes effect.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache: the Ed25519 kernel (127-iteration scan
# + decompression chain) costs tens of seconds to compile per bucket size
# on CPU; cache compiled programs across test runs under the one rule of
# util/jax_cache.py.
from stellar_core_tpu.util.jax_cache import enable_compile_cache  # noqa: E402
enable_compile_cache()
