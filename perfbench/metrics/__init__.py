"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``'s
``per_layer`` list, found by the metric's name. Each defines
``read(run) -> float | None`` over the run record of ``driver.Run``; a
reader that finds nothing to read returns None, and the metric is left out
of the result line."""
