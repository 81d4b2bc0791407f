"""A backlog cell runs end to end: every request is queued before the run,
the window opens once some have finished, and the rates count the work
done inside it."""
import json

import pytest

from benchutil import TINY_LIMITS, make_root, run_cell

TINY_DOCS = {"kind": "backlog", "documents": 24, "open_after_finished": 4,
             "prompt": {"median": 40, "sigma": 0.3, "lo": 16, "hi": 64},
             "output": {"median": 8, "sigma": 0.5, "lo": 4, "hi": 24}}


def _docs_root(tmp_path, mix=None):
    root = make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.docs", "config": "tiny",
                              "traffic": "tinydocs", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "qwen2-0.5b.docs" in m.get("workloads", []):
            m["workloads"].append("tiny.docs")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/traffic/tinydocs.json").write_text(
        json.dumps(mix or TINY_DOCS))
    (root / "bench/limits/tiny.docs.json").write_text(
        json.dumps(TINY_LIMITS))
    return root


def test_a_backlog_cell_reports_its_rates_and_is_correct(tmp_path):
    rc, out = run_cell(_docs_root(tmp_path), cell="tiny.docs", seconds=1.5)
    assert rc == 0 and out["correct"], out["checks"]
    assert set(out["metrics"]) == {"prompt_tok_s", "output_tok_s",
                                   "setup_s"}
    assert out["metrics"]["prompt_tok_s"]["value"] > 0
    assert out["metrics"]["output_tok_s"]["value"] > 0
    assert 0 < out["attempted"] <= TINY_DOCS["documents"]
    assert out["failed"] == 0


def test_a_warm_phase_that_never_reaches_its_count_raises(tmp_path,
                                                          monkeypatch):
    from bench import run

    monkeypatch.setattr(run, "WARM_LIMIT_S", 0.5)
    root = _docs_root(tmp_path, mix=dict(TINY_DOCS, documents=3))
    with pytest.raises(RuntimeError, match="warm phase"):
        run_cell(root, cell="tiny.docs", seconds=1.0)
