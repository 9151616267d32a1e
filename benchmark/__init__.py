"""The benchmark of stellar-core-tpu: see BENCHMARK.json and PERF.md."""
