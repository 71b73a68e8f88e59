"""The on-chip benchmark: see PERF.md and BENCHMARK.json at the repo root."""
