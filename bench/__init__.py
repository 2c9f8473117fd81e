"""On-chip benchmark of encrypted serving (see PERF.md and BENCHMARK.json)."""
