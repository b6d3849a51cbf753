"""On-chip benchmark of the served path (see BENCHMARK.json at the root)."""
