"""Chip benchmark of the Autumn store: one cell per run of ``bench/run.py``.

Everything that judges a run lives here and is kept apart from the
program: traffic generation, the plain reference, the trace reduction, the
byte counts and the table of peaks.  Configurations, traffic mixes and
per-layer metrics are files found by the names in ``BENCHMARK.json``.
"""
