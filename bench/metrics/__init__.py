"""One reader per per-layer metric: ``read(run) -> float | None`` where
``run`` is a ``bench.lib.harness.RunData``.  A reader that finds nothing
to read returns ``None`` and the metric is left out of the line."""
