"""The benchmark: one cell of ``BENCHMARK.json`` run once on the chip.

Everything that measures lives here: traffic generation, the seeded weights,
each model family's layout, counts and plain reference (``bench/families/``),
the comparison that decides ``correct``, the table of peaks and the rules
of the operation and byte counts, and the reduction from the profiler's
trace to per-layer metrics. ``bench/run.py`` is the entry point.
"""
