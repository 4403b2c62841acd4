"""A chip benchmark of the serving engine: see PERF.md and BENCHMARK.json."""
