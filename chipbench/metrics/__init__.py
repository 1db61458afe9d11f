"""One reader per metric, named as in ``BENCHMARK.json``.  Each has
``read(rec) -> float | None``: None where the run holds nothing to read."""
