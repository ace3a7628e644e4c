"""On-chip benchmark of the MoEless serving path (see BENCHMARK.json)."""
