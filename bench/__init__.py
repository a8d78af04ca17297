"""On-chip benchmark of the DeFT training runtime (see BENCHMARK.json)."""
