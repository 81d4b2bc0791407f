"""The harness finds a cell's configuration, mix, generator and metrics by
name, so a new one is new files and entries only; and a run refuses to
start without a TPU."""
import json

from benchutil import make_root, run_cell

NEW_GENERATOR = '''
"""Evenly spaced arrivals: a generator added as a file."""
import numpy as np

from bench.traffic import common


def make(mix, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    n = int(mix["rate_per_s"] * seconds)
    return common.Traffic([
        common.Item(i, i / mix["rate_per_s"],
                    rng.integers(0, vocab, mix["prompt"], dtype=np.int32),
                    mix["output"]) for i in range(n)])
'''
NEW_METRIC = '''
"""Requests finished inside the window."""


def read(ctx):
    return sum(1 for r in ctx.recs
               if r.done is not None and 0 <= r.done <= ctx.close)
'''


def test_new_config_mix_generator_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path, mix={"kind": "even", "rate_per_s": 8.0,
                                    "prompt": 20, "output": 6})
    (root / "bench/traffic/even.py").write_text(NEW_GENERATOR)
    (root / "bench/metrics/finished_n.py").write_text(NEW_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({
        "name": "finished_n", "unit": "requests", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, out = run_cell(root, seconds=1.5)
    assert rc == 0
    assert out["correct"], out["checks"]
    assert out["metrics"]["finished_n"]["value"] > 0
    assert out["metrics"]["finished_n"]["unit"] == "requests"
    for name in ("output_tok_s", "setup_s"):
        assert out["metrics"][name]["value"] > 0
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


def test_without_a_tpu_the_run_exits_nonzero_and_prints_nothing(tmp_path,
                                                                capsys):
    root = make_root(tmp_path)
    rc, out = run_cell(root, require_tpu=True)
    assert rc != 0 and out is None
    assert "no TPU" in capsys.readouterr().err


def test_a_cell_that_asks_for_more_chips_than_there_are_exits_nonzero(
        tmp_path):
    root = make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"][-1]["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, out = run_cell(root)
    assert rc != 0 and out is None
