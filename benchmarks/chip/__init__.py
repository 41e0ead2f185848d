"""On-chip benchmark of the R-Storm placement service (see BENCHMARK.json)."""
