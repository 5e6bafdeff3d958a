"""The on-chip benchmark: cells, metric readers, the trace reduction and
the float32 reference that decides ``correct`` (see BENCHMARK.json)."""
