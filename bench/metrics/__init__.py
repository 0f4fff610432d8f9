"""One reader per metric, found by the metric's name: ``read(run)`` takes a
``bench.harness.Run`` and returns the metric, or None where the run holds
nothing for it to read."""
