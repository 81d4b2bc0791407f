"""Finds what a cell names, by name: its configuration and the
configuration's model family, its traffic mix and generator, its metrics'
readers and its correctness limits.

A later change adds a configuration, a family, a mix, a generator or a
metric by adding a file and an entry in ``BENCHMARK.json``; nothing here
changes.

* configuration: ``bench/configs/<config>.json`` (the ``file`` of its entry)
* model family:  ``bench/families/<model_type>.py``, by the configuration's
                 published ``model["model_type"]`` (``bench/families/``
                 lists what a family gives)
* traffic mix:   ``bench/traffic/<traffic>.json``, generator
                 ``bench/traffic/<kind>.py`` with ``make(mix, seed, seconds,
                 vocab)``
* metric:        ``bench/metrics/<name>.py`` with ``read(ctx)``
* limits:        ``bench/limits/<workload>.json``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib


class Bench:
    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _named(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, cell: dict) -> dict:
        entry = self._named("configs", cell["config"])
        return json.loads((self.root / entry["file"]).read_text())

    def family(self, conf: dict):
        """The module of a configuration's model family."""
        return self._module("families", conf["model"]["model_type"])

    def mix(self, cell: dict) -> dict:
        return self._json("traffic", cell["traffic"])

    def limits(self, cell: dict) -> dict:
        return self._json("limits", cell["name"])

    def generator(self, mix: dict):
        return self._module("traffic", mix["kind"])

    def metrics(self, cell: dict, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer
        ones: those that list the cell, or list no cells."""
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.spec[key]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def _json(self, sub: str, name: str) -> dict:
        return json.loads((self.root / "bench" / sub / f"{name}.json")
                          .read_text())

    def _module(self, sub: str, name: str):
        path = self.root / "bench" / sub / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {sub} module {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
