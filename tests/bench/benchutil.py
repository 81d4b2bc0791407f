"""Helpers of the benchmark's CPU tests: a throwaway benchmark root that
holds a copy of ``bench/`` and a tiny Qwen2-family cell, and a way to run
one cell of it through ``bench/run.py`` on the CPU."""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_MODEL = {
    "model_type": "qwen2", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
}
TINY_ENGINE = {"page_size": 16, "slots": 4, "max_len": 96,
               "prefill_chunk_tokens": 32, "prompt_buckets": [16, 32],
               "kv_pages": 25, "prefix_cache_bytes": 1 << 20}
TINY_CHAT = {"kind": "open_loop", "rate_per_s": 10.0, "warm_s": 0.5,
             "prompt": {"median": 30, "sigma": 0.5, "lo": 8, "hi": 64},
             "output": {"median": 8, "sigma": 0.5, "lo": 4, "hi": 24}}
# the widest logit gap of the tiny bf16 model's served tokens, from CPU
# readings (tests/bench/test_bench_faults.py shows the control above it)
TINY_LIMITS = {"logit_gap": {"limit": 0.01}, "sample_tokens": 60,
               "checked_tokens_min": 20}


def make_root(tmp: pathlib.Path, *, model=None, engine=None, mix=None,
              limits=None) -> pathlib.Path:
    """A benchmark root with the repo's ``bench/`` and one more cell,
    ``tiny.chat``, built from new files only."""
    root = tmp / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.chat", "config": "tiny",
                              "traffic": "tinychat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    conf = {"model": model or TINY_MODEL, "engine": engine or TINY_ENGINE}
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    (root / "bench/traffic/tinychat.json").write_text(
        json.dumps(mix or TINY_CHAT))
    (root / "bench/limits/tiny.chat.json").write_text(
        json.dumps(limits or TINY_LIMITS))
    return root


def run_cell(root, cell="tiny.chat", seed=2**31 + 5, seconds=1.5, trace=0,
             require_tpu=False):
    """(exit code, parsed last stdout line or None) of one run on the
    CPU."""
    from bench import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_tpu=require_tpu,
                      compile_cache=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
