"""Per-layer metric readers: ``bench/metrics/<metric name>.py``.

Each file defines ``read(ctx) -> float | None``. A reader that finds
nothing to read returns None and the harness leaves the metric out.
"""
