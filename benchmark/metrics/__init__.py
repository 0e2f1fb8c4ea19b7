"""One reader per metric of BENCHMARK.json, ``<name>.py``, each with
``read(run) -> float | None`` over a :class:`benchmark.run.Run`.  A reader
that finds nothing to read returns None and the metric is left out."""
