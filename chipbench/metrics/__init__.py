"""Per-layer metric readers: one module per metric, found by its name in
BENCHMARK.json. Each has ``read(ctx)`` (``ctx`` is ``chipbench.cell.Context``)
and returns the number, or None where it finds nothing to read."""
