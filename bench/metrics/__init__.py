"""One reader per metric, found by the metric's name up to its first dot.

Each module's ``read(run)`` takes a ``bench.harness.Run`` and returns the
number, or None where the run holds nothing to read it from.
"""
