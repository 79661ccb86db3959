"""Chip benchmark of the PARAFAC2 fit: see BENCHMARK.json and PERF.md."""
