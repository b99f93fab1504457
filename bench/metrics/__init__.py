"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module defines ``read(run) -> float | None`` over the ``Run`` that
``bench/harness.py`` measured.  A reader that finds nothing to read
returns None, and the harness leaves the metric out of the line.
"""
