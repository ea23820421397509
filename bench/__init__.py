"""Chip benchmark of the gZ compressed-collective library (see BENCHMARK.json)."""
