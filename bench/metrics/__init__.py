"""Per-layer metric readers, one module per metric, found by name.

Each module's ``read(run)`` takes a ``run.RunData`` and returns the
metric's value, or None when the run holds nothing to read it from, in
which case the harness leaves the metric out of the result line."""
