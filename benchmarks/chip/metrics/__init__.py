"""Per-layer metrics, one reader per file, found by the metric's name.

Each module has ``read(view) -> float | None`` over a
``harness.RunView``; ``None`` means the run holds nothing to read, and
the harness then leaves the metric out of the result line.
"""
