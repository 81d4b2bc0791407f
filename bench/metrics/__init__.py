"""One reader per metric, ``<metric name>.py``, found by name. Each has
``read(ctx) -> float | None``; None leaves the metric out of the line.

``ctx`` holds ``cell``, ``conf`` (the configuration file: config.json keys
under ``model``, geometry under ``engine``), ``family`` (the module of its
model family, ``bench/families/``, which gives the counts), ``close`` (the
window's length, s), ``recs`` and ``prefill`` (what the client saw,
``bench.window``), ``spans`` (host-clock set-up parts, s), ``trace``
(``bench.trace.Trace`` in a traced run, else None), ``peaks`` (the device's
row of ``peaks.json``, None off a chip) and ``manifest`` (the deployment's
tier bindings)."""
