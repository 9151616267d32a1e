"""Where JAX's persistent compilation cache lives: one rule for the
node, the tests, the bench and the scripts.

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this module
  sets NO directory in code, so whoever starts the process (an
  operator, a harness, a sealed chip machine that mounts a warm cache)
  places the cache from outside.
- unset: `<checkout>/.jax_compile_cache/<platform>` (gitignored). The
  path is part of the cache key, so it is fixed: never a temporary
  name, a pid or a time. The platform sub-directory keeps XLA:CPU AOT
  artifacts (which embed host machine features and do not transfer
  between host generations) apart from chip executables.

`cache_dir_for_backend` asks `jax.default_backend()`, which starts the
backend and on a chip machine TAKES THE CHIP: only a process that is
meant to own the chip may call it. (`chip_smoke.py`, which must stay
off JAX and stand alone, restates `CACHE_ROOT` to count entries.)
"""

from __future__ import annotations

import os
import platform as _platform
from typing import Optional

CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def cache_dir_for_backend() -> str:
    """`CACHE_ROOT`/<backend>[-<machine>] — resolved after backend
    init (see the module docstring: this takes the chip)."""
    import jax
    backend = jax.default_backend()
    if backend == "cpu":
        backend = "cpu-" + _platform.machine()
    return os.path.join(CACHE_ROOT, backend)


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on under the rule above. Returns the
    directory this module chose, or None when the environment placed
    the cache and nothing was set here."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    d = cache_dir_for_backend()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    return d
