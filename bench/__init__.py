"""The benchmark: one cell of ``BENCHMARK.json`` run once on the chip.

Everything that measures lives here: traffic generation, the seeded weights,
the plain reference and the comparison that decides ``correct``, the table
of peaks, the operation and byte counts, and the reduction from the
profiler's trace to per-layer metrics. ``bench/run.py`` is the entry point.
"""
