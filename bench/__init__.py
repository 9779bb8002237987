"""On-chip benchmark of the SlimAdam trainer and the paged server (see BENCHMARK.json)."""
