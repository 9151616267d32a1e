"""JAX's Python tracing and lowering in this process, set-up included
(s): totals of the program's process-wide `jax.trace` and `jax.lower`
zones. No cache holds this work: every process pays it per shape."""


def read(cell):
    from stellar_core_tpu.util.perf import default_registry
    report = default_registry.report()
    parts = [report[z]["total_ms"] for z in ("jax.trace", "jax.lower")
             if z in report]
    if not parts:
        return None
    return sum(parts) / 1e3
