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

`enable_compile_cache` also registers, once per process, the listeners
that put JAX's own work into `perf.default_registry` (process-wide:
JAX's caches are), so that a node which meets a new shape can say what
stalled it:

- zones `jax.trace`, `jax.lower`, `jax.backendCompile`: count and
  seconds of Python tracing, lowering to MLIR, and XLA compilation (or
  reading a compiled program back from the persistent cache). Only
  the outermost trace counts: tracing the verify kernel traces some
  24,000 `jnp` calls inside it, each an event of its own, nested in
  the kernel's;
- zones `jax.compileCache.hit` / `.miss`: counts of persistent-cache
  lookups (0 seconds);
- while any FlightRecorder is on, an instant `jax.compile` in each
  (args `fun`, `stage`, `seconds`) per trace, lowering and compile.

`cache_dir_for_backend` asks `jax.default_backend()`, which starts the
backend and on a chip machine TAKES THE CHIP: only a process that is
meant to own the chip may call it.
"""

from __future__ import annotations

import os
import platform as _platform
import threading
from typing import Optional

# jax.monitoring event -> zone of perf.default_registry
_DURATION_ZONES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backendCompile",
}
_COUNT_ZONES = {
    "/jax/compilation_cache/cache_hits": "jax.compileCache.hit",
    "/jax/compilation_cache/cache_misses": "jax.compileCache.miss",
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_listening = False
_tracing = threading.local()    # .depth: traces open on this thread

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


def _on_scalar(event: str, value, **kw) -> None:
    # JAX reports the start of a timed scope as a scalar
    if event == _TRACE_EVENT:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_duration(event: str, duration: float, **kw) -> None:
    zone = _DURATION_ZONES.get(event)
    if zone is None:
        return
    if event == _TRACE_EVENT:
        depth = _tracing.depth = max(
            0, getattr(_tracing, "depth", 1) - 1)
        if depth > 0:
            return
    from . import perf, tracing
    perf.default_registry.add(zone, duration)
    if tracing.ENABLED:
        args = {"fun": kw.get("fun_name"), "stage": zone[4:],
                "seconds": duration}
        for rec in tracing.active_recorders():
            rec.instant("jax.compile", args)


def _on_event(event: str, **kw) -> None:
    zone = _COUNT_ZONES.get(event)
    if zone is not None:
        from . import perf
        perf.default_registry.add(zone, 0.0)


def watch_jax_compiles() -> None:
    """Register the listeners above, once per process."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on under the rule above. Returns the
    directory this module chose, or None when the environment placed
    the cache and nothing was set here."""
    import jax
    watch_jax_compiles()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    d = cache_dir_for_backend()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    return d
