"""The serving step's own telemetry: the ``serve.*`` host spans it writes
into a profiler trace, and the counters ``prefill_real_tokens`` and
``decode_rows`` in ``ServingEngine.stats``, on the paged path, the
contiguous fused path and the legacy host loop."""
import functools
import glob
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import transformer
from repro.serving.engine import Request, ServingEngine
from repro.serving.speculative import SpecConfig

PATHS = {
    "paged": {"page_size": 8},
    "contiguous": {},
    "host_loop": {"fused": False},
}


@functools.lru_cache(maxsize=1)
def _model():
    cfg = configs.get_config("qwen2-0.5b-smoke")
    return cfg, transformer.init_model(jax.random.key(0), cfg)


def _engine(**kw):
    cfg, params = _model()
    kw = {"slots": 2, "max_len": 48, "prompt_buckets": (8, 16, 32), **kw}
    return ServingEngine(cfg, params, **kw)


def _requests(seed=3, n=7, shared=9):
    """Prompts of 1-22 tokens (every other one behind a shared system
    prompt) asking for 1-6 tokens."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, 256, shared).tolist()
    out = []
    for i in range(n):
        body = rng.integers(0, 256, int(rng.integers(1, 14))).tolist()
        prompt = np.asarray(head + body if i % 2 == 0 else body, np.int32)
        out.append(Request(request_id=i, prompt=prompt,
                           max_new_tokens=1 + i % 6))
    return out


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    res = eng.run_to_completion()
    assert sorted(res) == [r.request_id for r in reqs]
    return res


@pytest.mark.parametrize("path", sorted(PATHS))
def test_prefill_real_tokens_are_the_prompts(path):
    eng = _engine(**PATHS[path])
    reqs = _requests()
    _drain(eng, reqs)
    assert eng.stats["prefill_real_tokens"] == sum(
        len(r.prompt) for r in reqs)
    assert eng.stats["prefill_real_tokens"] < eng.stats["prefill_tokens"]


@pytest.mark.parametrize("path", ["paged", "contiguous"])
def test_prefill_real_tokens_leave_out_prefix_hits(path):
    eng = _engine(prefix_cache_bytes=8 << 20, **PATHS[path])
    reqs = _requests()
    _drain(eng, reqs)
    hits = eng.stats["prefix_hit_tokens"]
    assert hits > 0
    assert eng.stats["prefill_real_tokens"] == sum(
        len(r.prompt) for r in reqs) - hits


@pytest.mark.parametrize("path", sorted(PATHS))
def test_decode_rows_are_the_tokens_decode_made(path):
    eng = _engine(sync_every=1, **PATHS[path])
    res = _drain(eng, _requests())
    made = sum(len(r.tokens) - 1 for r in res.values())
    assert made > 0
    assert eng.stats["decode_rows"] == made
    assert eng.stats["decode_rows"] <= eng.stats["decode_steps"] * eng.slots


def test_decode_rows_count_speculative_slot_steps():
    eng = _engine(spec=SpecConfig(k=2, proposer="ngram"))
    res = _drain(eng, _requests())
    emitted = eng.stats["spec_emitted"]
    assert emitted == sum(len(r.tokens) - 1 for r in res.values())
    assert 0 < eng.stats["decode_rows"] <= emitted
    assert eng.spec_summary()["tokens_per_slot_step"] == round(
        emitted / eng.stats["decode_rows"], 4)


@pytest.mark.parametrize("kw", [
    {"page_size": 8, "prefix_cache_bytes": 8 << 20},
    # a pool of 9 pages: requests are preempted and prefilled again
    {"page_size": 8, "kv_pages": 9, "prefill_chunk_tokens": 8},
    {"prefix_cache_bytes": 8 << 20},
], ids=["paged-cache", "paged-tight-pool", "contiguous-cache"])
def test_real_prefill_tokens_never_exceed_the_padded(kw):
    eng = _engine(**kw)
    for r in _requests(n=9):
        eng.submit(r)
    for _ in range(500):
        if not (eng.queue or any(a is not None for a in eng.active)):
            break
        eng.step()
        assert 0 < eng.stats["prefill_real_tokens"] \
            <= eng.stats["prefill_tokens"]
    assert len(eng.results) == 9


def test_counters_nothing_read_are_gone():
    stats = _engine(page_size=8).stats
    for gone in ("ttft_sum_s", "decode_sum_s", "host_syncs_admit",
                 "spec_slot_steps"):
        assert gone not in stats
    assert stats["prefill_real_tokens"] == stats["decode_rows"] == 0


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def _warm(eng):
    """Compile the programs one step will run, outside the trace."""
    _drain(eng, [Request(request_id=100, prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=3)])


def _traced_step(eng, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("path", ["paged", "contiguous"])
def test_one_step_writes_nested_serve_spans(path, tmp_path):
    eng = _engine(sync_every=1, prefix_cache_bytes=8 << 20, **PATHS[path])
    _warm(eng)
    rng = np.random.default_rng(5)
    for i, n in enumerate((6, 7)):
        eng.submit(Request(request_id=i, prompt=rng.integers(0, 256, n),
                           max_new_tokens=3))
    events = _traced_step(eng, tmp_path)
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    (step,) = by["serve.step"]
    for name in ("serve.admit", "serve.prefill", "serve.decode",
                 "serve.flush", "serve.sync", "serve.prefix"):
        assert by.get(name), f"no {name} span in {sorted(by)}"
        assert all(_inside(ev, step) for ev in by[name]), name
    (flush,) = by["serve.flush"]
    assert any(_inside(ev, flush) for ev in by["serve.sync"])
    (prefill,) = by["serve.prefill"]
    assert prefill[3] == {"rows": 2, "npad": 2, "cw": 8}
    if path == "contiguous":
        (admit,) = by["serve.admit"]
        assert _inside(prefill, admit)
    (decode,) = by["serve.decode"]
    assert decode[3]["rows"] == 2


def test_spans_add_no_device_fetch(monkeypatch):
    """A paged step fetches from the device once for its flush, and once
    more when a prompt completes (its first token): the spans add none."""
    eng = _engine(page_size=8, sync_every=1)
    _warm(eng)
    syncs = eng.stats["host_syncs_decode"]
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    eng.submit(Request(request_id=0, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=4))
    eng.step()  # admit, prefill to the first token, one decode step
    assert len(calls) == 2
    eng.step()  # one decode step
    assert len(calls) == 3
    assert eng.stats["host_syncs_decode"] - syncs == 2
