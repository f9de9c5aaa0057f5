"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``. Each has ``read(outcome, run) -> float | None``
and returns None where the run gave it nothing to read."""
