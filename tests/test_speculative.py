"""Speculative decoding subsystem tests.

Covers the lossless rejection-sampling rule (distributional + greedy
reduction), the prompt-lookup proposer, engine-level greedy byte-parity
across verify strategies (parallel chunk for attention archs, stepwise
snapshot rollback for recurrent ones), the draft-model proposer's cache
alignment, EOS/budget truncation inside a verified chunk, lease metering of
drafted-but-rejected work, latency telemetry, and the scalar-vs-batched
sampling parity sweep (the top_k clamp bugfix).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import recompile, scheduler
from repro.core.invocation import InvocationService
from repro.models import transformer
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import (SamplingConfig, SamplingParams,
                                    accept_speculative, sample,
                                    sample_batched, spec_target_probs)
from repro.serving.service import serving_container
from repro.serving.speculative import (DraftModelProposer, NGramProposer,
                                       SpecConfig, has_recurrent_state)


@functools.lru_cache(maxsize=4)
def _model(arch="qwen2-0.5b-smoke"):
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(0), cfg)
    return cfg, params


def _stream(cfg, n=6, max_new=10, seed=0, temperature=0.0, eos=None,
            shared_prefix=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, (shared_prefix,), dtype=np.int32)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 12))
        p = rng.integers(0, cfg.vocab_size, (plen,), dtype=np.int32)
        if shared_prefix and i % 2 == 0:
            p = np.concatenate([shared, p])
        reqs.append(Request(request_id=i, prompt=p,
                            max_new_tokens=int(rng.integers(2, max_new + 1)),
                            sampling=SamplingConfig(temperature=temperature),
                            eos_id=eos))
    return reqs


def _serve(cfg, params, reqs, spec=None, proposer=None, slots=2, max_len=64,
           **kw):
    eng = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                        prompt_buckets=(8, 16, 32), spec=spec,
                        proposer=proposer, **kw)
    for r in reqs:
        eng.submit(r)
    res = eng.run_to_completion()
    assert eng.stats["unserved"] == 0
    return {k: res[k].tokens for k in sorted(res)}, eng


# ---------------------------------------------------------------------------
# The rejection-sampling rule
# ---------------------------------------------------------------------------
def test_accept_residual_identity():
    """The implemented rule is lossless by construction: for ANY proposal q,
    q(t)·min(1, p(t)/q(t)) + P(reject)·residual(t) == p(t), with p the SAME
    modified (temperature/top-k) distribution sample_batched draws from."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(3, 2, 16)), jnp.float32)
    params = SamplingParams(jnp.asarray([0.7, 1.3, 1.0]),
                            jnp.asarray([0, 5, 3], jnp.int32))
    p = np.asarray(spec_target_probs(logits, params))[:, 0]  # (3, V)
    q = rng.dirichlet(np.ones(16), size=3)
    accept = np.minimum(1.0, p / np.maximum(q, 1e-30))
    alpha = (q * accept).sum(-1, keepdims=True)
    residual = np.maximum(p - q, 0.0)
    residual /= residual.sum(-1, keepdims=True)
    emitted = q * accept + (1.0 - alpha) * residual
    np.testing.assert_allclose(emitted, p, atol=1e-6)


def test_accept_point_mass_distribution_monte_carlo():
    """Deterministic (point-mass) proposers: the first emitted token is
    still distributed exactly as the target. Monte Carlo over a large
    batch of identical rows with a fixed key — deterministic, not flaky."""
    v, n = 8, 8000
    rng = np.random.default_rng(1)
    row = rng.normal(size=(v,)).astype(np.float32)
    logits = jnp.broadcast_to(jnp.asarray(row), (n, 2, v))
    drafts = jnp.full((n, 1), 3, jnp.int32)
    ndraft = jnp.ones((n,), jnp.int32)
    params = SamplingParams(jnp.ones((n,)), jnp.zeros((n,), jnp.int32))
    out, acc = accept_speculative(jax.random.key(7), logits, drafts, ndraft,
                                  params)
    first = np.asarray(out[np.arange(n), 0])
    # rejected rows emit the resample at position 0; accepted rows emit the
    # draft there — either way out[:, 0] is the first emitted token
    p1 = SamplingParams(params.temperature[:1], params.top_k[:1])
    p = np.asarray(spec_target_probs(logits[:1], p1)[0, 0])
    emp = np.bincount(first, minlength=v) / n
    assert np.abs(emp - p).max() < 4.0 / np.sqrt(n)
    # acceptance of draft 3 should match p(3) (q is a point mass)
    assert abs(np.mean(np.asarray(acc) == 1) - p[3]) < 4.0 / np.sqrt(n)


def test_accept_greedy_reduces_to_prefix_match():
    v = 11
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(2, 4, v)), jnp.float32)
    arg = np.asarray(jnp.argmax(logits, -1))  # (2, 4)
    # row 0: drafts match argmax for 2 positions then diverge
    drafts = np.zeros((2, 3), np.int32)
    drafts[0] = [arg[0, 0], arg[0, 1], (arg[0, 2] + 1) % v]
    drafts[1] = [(arg[1, 0] + 1) % v, arg[1, 1], arg[1, 2]]
    params = SamplingParams(jnp.zeros((2,)), jnp.zeros((2,), jnp.int32))
    out, acc = accept_speculative(
        jax.random.key(0), logits, jnp.asarray(drafts),
        jnp.full((2,), 3, jnp.int32), params)
    out, acc = np.asarray(out), np.asarray(acc)
    assert acc.tolist() == [2, 0]
    # emitted = accepted drafts + argmax at the boundary, zeros after
    assert out[0].tolist() == [arg[0, 0], arg[0, 1], arg[0, 2], 0]
    assert out[1].tolist() == [arg[1, 0], 0, 0, 0]


def test_accept_ndraft_masks_tail():
    v = 5
    logits = jnp.zeros((1, 4, v), jnp.float32)
    drafts = jnp.zeros((1, 3), jnp.int32)  # argmax(0s) == 0 -> all "match"
    params = SamplingParams(jnp.zeros((1,)), jnp.zeros((1,), jnp.int32))
    for nd in range(4):
        _, acc = accept_speculative(jax.random.key(0), logits, drafts,
                                    jnp.asarray([nd], jnp.int32), params)
        assert int(acc[0]) == nd  # never accepts past the real drafts


# ---------------------------------------------------------------------------
# NGram proposer
# ---------------------------------------------------------------------------
def test_ngram_lookup_drafts_continuation():
    prop = NGramProposer(4, ngram_max=3, ngram_min=1)
    h = np.asarray([5, 6, 7, 8, 9, 5, 6, 7], np.int32)
    # suffix [5,6,7] occurred at 0; continuation is [8, 9, 5, 6]
    assert prop.lookup(h, 4).tolist() == [8, 9, 5, 6]
    # no repetition at all -> no draft
    assert prop.lookup(np.arange(8, dtype=np.int32), 4).size == 0
    # prefers an occurrence with a full k continuation over the most recent
    h2 = np.asarray([1, 2, 3, 4, 1, 2, 1, 2], np.int32)
    d = prop.lookup(h2, 3)
    assert d.tolist() == [3, 4, 1]


# ---------------------------------------------------------------------------
# Engine parity: speculative greedy streams are byte-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen2-0.5b-smoke",
                                  "recurrentgemma-9b-smoke"])
@pytest.mark.parametrize("k", [1, 3])
def test_spec_greedy_byte_identical(arch, k):
    cfg, params = _model(arch)
    reqs = _stream(cfg, n=6, max_new=10, shared_prefix=6)
    base, _ = _serve(cfg, params, reqs)
    out, eng = _serve(cfg, params, reqs, spec=SpecConfig(k=k))
    assert out == base
    assert eng.stats["spec_steps"] > 0
    # the verify strategy must match the arch's state structure
    assert has_recurrent_state(cfg) == (arch != "qwen2-0.5b-smoke")


def test_spec_eos_truncates_inside_chunk():
    cfg, params = _model()
    base, _ = _serve(cfg, params, _stream(cfg, n=4, max_new=12))
    # choose an eos that actually appears mid-stream in the baseline
    eos = base[1][len(base[1]) // 2]
    reqs = _stream(cfg, n=4, max_new=12, eos=eos)
    b2, _ = _serve(cfg, params, reqs)
    o2, _ = _serve(cfg, params, reqs, spec=SpecConfig(k=4))
    assert o2 == b2
    assert any(toks[-1] == eos and len(toks) < 12 for toks in b2.values())


def test_spec_with_prefix_cache_byte_identical():
    cfg, params = _model()
    reqs = _stream(cfg, n=8, max_new=8, shared_prefix=10)
    base, _ = _serve(cfg, params, reqs)
    out, eng = _serve(cfg, params, reqs, spec=SpecConfig(k=3),
                      prefix_cache_bytes=1 << 20)
    assert out == base
    assert eng.stats["prefix_hits"] > 0  # both subsystems actually engaged
    assert eng.stats["spec_accepted"] > 0


def test_spec_temperature_rows_serve_and_respect_budget():
    """Stochastic rows are lossless distributionally (proved above); here
    the engine contract: correct token counts, vocab-range tokens, retired
    slots recycled."""
    cfg, params = _model()
    reqs = _stream(cfg, n=6, max_new=8, temperature=0.8)
    out, eng = _serve(cfg, params, reqs, spec=SpecConfig(k=3))
    for r in reqs:
        toks = out[r.request_id]
        assert len(toks) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab_size for t in toks)
    assert eng.stats["spec_steps"] > 0


# ---------------------------------------------------------------------------
# Draft-model proposer
# ---------------------------------------------------------------------------
def test_draft_model_self_draft_accepts_everything():
    """Draft == target (same params): greedy drafts must all be accepted —
    any rejection would mean the draft cache and the target cache disagree
    about the same computation (a rollback/alignment bug)."""
    cfg, params = _model()
    reqs = _stream(cfg, n=5, max_new=9)
    base, _ = _serve(cfg, params, reqs)
    prop = DraftModelProposer(cfg, params, 4)
    out, eng = _serve(cfg, params, reqs,
                      spec=SpecConfig(k=4, proposer="draft",
                                      draft_arch="qwen2-0.5b-smoke"),
                      proposer=prop)
    assert out == base
    sm = eng.spec_summary()
    assert sm["proposer"] == "draft"
    assert sm["acceptance_rate"] == 1.0, sm


def test_draft_model_rejects_recurrent_and_vocab_mismatch():
    cfg, params = _model()
    rcfg, rparams = _model("recurrentgemma-9b-smoke")
    with pytest.raises(NotImplementedError):
        DraftModelProposer(rcfg, rparams, 2)
    prop = DraftModelProposer(cfg, params, 2)

    class _FakeEngine:
        class cfg:
            vocab_size = cfg.vocab_size + 1  # vocab mismatch

    with pytest.raises(AssertionError):
        prop.bind(_FakeEngine())


# ---------------------------------------------------------------------------
# Engine guards + manifest surfacing
# ---------------------------------------------------------------------------
def test_spec_requires_fused_and_text_frontend():
    cfg, params = _model()
    with pytest.raises(ValueError):
        ServingEngine(cfg, params, slots=2, max_len=32, fused=False,
                      spec=SpecConfig(k=2))
    acfg = configs.get_config("musicgen-medium-smoke")
    aparams = transformer.init_model(jax.random.key(0), acfg)
    with pytest.raises(NotImplementedError):
        ServingEngine(acfg, aparams, slots=2, max_len=32,
                      spec=SpecConfig(k=2))


def test_spec_overrides_sync_every():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        spec=SpecConfig(k=2), sync_every=4)
    assert eng.sync_every == 1


def test_manifest_gains_speculative_section():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        spec=SpecConfig(k=2),
                        manifest={"container": "c", "apis": {}})
    assert eng.manifest["speculative"] == {"proposer": "ngram", "k": 2}


# ---------------------------------------------------------------------------
# Lease metering: drafted-but-rejected FLOPs land on the bill
# ---------------------------------------------------------------------------
def test_executor_bills_spec_verify_positions():
    cfg, params = _model()
    cont = serving_container(cfg, params, slots=2, max_len=64,
                             prompt_buckets=(8, 16, 32),
                             spec=SpecConfig(k=3))
    service = InvocationService(scheduler.Cluster(chips=1))
    with service.acquire_serving("tenant-a", cont,
                                 recompile.PORTABLE_CPU) as ex:
        for r in _stream(cfg, n=4, max_new=8):
            ex.submit(r)
        results = ex.run()
        stats = dict(ex.engine.stats)
    tokens = sum(len(r.tokens) for r in results.values())
    # every emitted token is on the tenant ledger...
    assert service.meter.served_tokens("tenant-a") == tokens
    # ...and the lease was billed per verified POSITION (k+1 per step),
    # which strictly exceeds emitted-token needs whenever a draft was
    # rejected — the tenant pays for the gamble, not just the win
    verify = service.meter.total_steps("serve_spec_verify", "tenant-a")
    assert verify == stats["spec_positions"] > 0
    assert verify >= stats["spec_emitted"]
    assert service.meter.total_steps("serve_decode", "tenant-a") == 0
    service.meter.check_invariants()


# ---------------------------------------------------------------------------
# Latency telemetry (TTFT / TPOT satellite)
# ---------------------------------------------------------------------------
def test_request_latency_telemetry():
    cfg, params = _model()
    _, eng = _serve(cfg, params, _stream(cfg, n=4, max_new=6))
    for res in eng.results.values():
        assert res.ttft_s > 0
        if len(res.tokens) > 1:
            assert res.decode_s > 0
            assert res.tpot_s == pytest.approx(
                res.decode_s / (len(res.tokens) - 1))
    lat = eng.latency_summary()
    assert lat["requests"] == 4
    assert lat["ttft_p95_s"] >= lat["ttft_p50_s"] > 0
    assert lat["tpot_p95_s"] >= lat["tpot_p50_s"] > 0


def test_fleet_report_spec_and_latency():
    """Fleet surface: per-replica acceptance telemetry + aggregate, and the
    real-wall-clock TTFT/TPOT percentiles, all through one report."""
    from repro import fleet as fl

    cfg, params = _model()
    trace = fl.steady_trace(seed=3, duration_s=4.0, prompt_median=8,
                            prompt_lo=4, prompt_hi=12, max_new_lo=4,
                            max_new_hi=8)
    reqs = fl.materialize(trace, vocab_size=cfg.vocab_size, seed=4)
    fm = fl.FleetManager.build(
        cfg, params, chips=2,
        fleet=fl.FleetConfig(min_replicas=1, max_replicas=2, slots=2,
                             max_len=64, prompt_buckets=(8, 16, 32),
                             spec_k=2, prefix_cache_mb=1.0))
    report = fm.run_trace(reqs)
    assert report.served == report.requests
    assert report.reconciled
    sp = report.speculative
    assert sp["enabled"] and sp["drafted"] > 0
    assert 0 <= sp["acceptance_rate"] <= 1
    for rep in report.replicas:
        assert rep["spec"] is not None and rep["spec"]["k"] == 2
    assert report.ttft_p95_s >= report.ttft_p50_s > 0
    assert report.tpot_p95_s >= 0


# ---------------------------------------------------------------------------
# Satellite bugfix: scalar sample() top_k clamp parity with sample_batched
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("top_k", [0, 1, 7, 14])  # {0, 1, V, V+7}, V=7
def test_scalar_vs_batched_topk_parity(top_k):
    v = 7
    logits = jnp.asarray(
        np.random.default_rng(5).normal(size=(v,)), jnp.float32)
    cfg = SamplingConfig(temperature=0.8, top_k=top_k)
    params = SamplingParams.from_configs([cfg])
    for seed in range(8):
        key = jax.random.key(seed)
        a = int(sample(key, logits, cfg))
        b = int(sample_batched(key, logits[None], params)[0])
        assert a == b, (top_k, seed)


def test_scalar_topk_overflow_matches_full_distribution():
    """Pre-fix, top_k in (V, 2V) wrapped the negative sort index and
    silently masked the BOTTOM of the distribution; clamped, it must equal
    the full-distribution draw."""
    v = 7
    logits = jnp.asarray(
        np.random.default_rng(6).normal(size=(v,)), jnp.float32)
    for seed in range(8):
        key = jax.random.key(seed)
        full = int(sample(key, logits, SamplingConfig(temperature=1.0)))
        over = int(sample(key, logits,
                          SamplingConfig(temperature=1.0, top_k=v + 3)))
        assert full == over
