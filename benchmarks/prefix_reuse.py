"""Prefix-cache benchmark: shared-system-prompt and multi-turn traces,
cache-enabled vs cache-disabled, at exact token parity.

Measures the one number that matters — **prefill compute** (padded token
positions run through the prefill program, a machine-independent FLOP proxy:
every padded position costs the same per-layer work) — plus wall time and
hit rates for context, then drives a 2-replica fleet over a shared-prefix
trace to show router prefix affinity and per-replica hit rates end-to-end.

ASSERTS (the paper's lean-invocation claim, made falsifiable):
  * >= 2x prefill-compute reduction on the shared-prefix trace,
  * byte-identical token streams with the cache on vs off,
  * nonzero router prefix-affinity routes and per-replica hits in the fleet.

Writes machine-readable results to ``BENCH_prefix.json`` (``--out``).

    PYTHONPATH=src python benchmarks/prefix_reuse.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import configs
from repro.core import recompile
from repro.models import transformer
from repro.serving.engine import Request, ServingEngine


def _shared_prefix_stream(vocab: int, *, requests: int, prefix_len: int,
                          tail_lo: int, tail_hi: int, max_new: int,
                          seed: int = 0):
    """The canonical serving workload: one system prompt, many user tails."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, vocab, (prefix_len,), dtype=np.int32)
    out = []
    for i in range(requests):
        tail = rng.integers(0, vocab, (int(rng.integers(tail_lo, tail_hi + 1)),),
                            dtype=np.int32)
        out.append((np.concatenate([sys_prompt, tail]), max_new))
    return out


def _multi_turn_stream(vocab: int, *, sessions: int, turns: int,
                       turn_len: int, max_new: int, seed: int = 1):
    """Conversations: each turn's prompt extends the previous turn's."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(sessions):
        hist = rng.integers(0, vocab, (turn_len,), dtype=np.int32)
        out.append((hist, max_new))
        for _ in range(turns - 1):
            hist = np.concatenate(
                [hist, rng.integers(0, vocab, (turn_len,), dtype=np.int32)])
            out.append((hist, max_new))
    return out


def bench_engine(cfg, params, stream, *, cache_bytes, slots, max_len,
                 buckets) -> dict:
    engine = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                           prompt_buckets=buckets,
                           prefix_cache_bytes=cache_bytes)
    engine.warmup()
    warm = dict(engine.stats)
    t0 = time.perf_counter()
    for i, (prompt, max_new) in enumerate(stream):
        engine.submit(Request(request_id=i, prompt=prompt,
                              max_new_tokens=max_new))
        engine.run_to_completion()  # arrival order preserved (multi-turn)
    wall = time.perf_counter() - t0
    res = {k: engine.results[k].tokens for k in sorted(engine.results)}
    hits, misses = engine.stats["prefix_hits"], engine.stats["prefix_misses"]
    return {
        "mode": "cached" if cache_bytes else "uncached",
        "wall_s": round(wall, 4),
        "tokens": sum(len(t) for t in res.values()),
        "prefill_tokens": engine.stats["prefill_tokens"] - warm["prefill_tokens"],
        "prefill_calls": engine.stats["prefill_calls"] - warm["prefill_calls"],
        "prefix_hits": hits,
        "prefix_misses": misses,
        "hit_rate": round(hits / max(hits + misses, 1), 4),
        "prefix_hit_tokens": engine.stats["prefix_hit_tokens"],
        "cache": engine.prefix_cache.report() if engine.prefix_cache else None,
        "results": res,
    }


def bench_scenario(name, cfg, params, stream, *, slots, max_len, buckets,
                   cache_bytes) -> dict:
    off = bench_engine(cfg, params, stream, cache_bytes=None, slots=slots,
                       max_len=max_len, buckets=buckets)
    on = bench_engine(cfg, params, stream, cache_bytes=cache_bytes,
                      slots=slots, max_len=max_len, buckets=buckets)
    assert on["results"] == off["results"], (
        f"{name}: token parity broken — the cache changed served tokens")
    reduction = off["prefill_tokens"] / max(on["prefill_tokens"], 1)
    row = {
        "scenario": name,
        "requests": len(stream),
        "prefill_tokens_uncached": off["prefill_tokens"],
        "prefill_tokens_cached": on["prefill_tokens"],
        "prefill_reduction": round(reduction, 3),
        "wall_s_uncached": off["wall_s"],
        "wall_s_cached": on["wall_s"],
        "hit_rate": on["hit_rate"],
        "prefix_hit_tokens": on["prefix_hit_tokens"],
        "evictions": on["cache"]["evictions"],
        "token_parity": True,
    }
    print(f"  {name:<14} prefill tokens {off['prefill_tokens']:>6} -> "
          f"{on['prefill_tokens']:>6}  ({reduction:.2f}x less compute)  "
          f"hit rate {on['hit_rate']:.0%}  wall {off['wall_s']:.2f}s -> "
          f"{on['wall_s']:.2f}s")
    return row


def bench_fleet(cfg, params, *, smoke: bool, seed: int = 0) -> dict:
    """Shared-prefix trace through the elastic fleet: the router's prefix
    affinity steers prompt families to the replica holding their prefix."""
    from repro import fleet as fl

    trace = fl.steady_trace(seed=seed, duration_s=8.0 if smoke else 16.0,
                            rate=2.0, prompt_median=6, prompt_lo=3,
                            prompt_hi=10, max_new_lo=3, max_new_hi=6,
                            new_session_p=0.5)
    reqs = fl.materialize(trace, vocab_size=cfg.vocab_size, seed=seed + 1,
                          shared_prefix_len=10, multi_turn=True,
                          max_prompt_len=40)
    fleet_cfg = fl.FleetConfig(min_replicas=2, max_replicas=2, slots=2,
                               max_len=64, prompt_buckets=(8, 16, 32, 48),
                               tick_s=0.1, prefix_cache_mb=16.0)
    fm = fl.FleetManager.build(cfg, params, chips=2, fleet=fleet_cfg)
    report = fm.run_trace(reqs)
    pc = report.prefix_cache
    per_replica = {r["id"]: r["prefix"] for r in report.replicas
                   if r["prefix"] is not None}
    print(f"  fleet          {report.served}/{report.requests} served | "
          f"prefix-affinity routes {pc['prefix_affinity_routes']} | "
          f"hit rate {pc['hit_rate']:.0%} "
          f"({pc['hit_tokens']} tokens restored across "
          f"{len(per_replica)} replicas)")
    assert report.served == report.requests
    assert pc["prefix_affinity_routes"] > 0, (
        "router never used prefix affinity on a shared-prefix trace")
    assert pc["hits"] > 0 and any(
        p["hits"] > 0 for p in per_replica.values()), (
        "no per-replica prefix-cache hits on a shared-prefix trace")
    return {
        "requests": report.requests,
        "prefix_affinity_routes": pc["prefix_affinity_routes"],
        "session_affinity_routes": pc["session_affinity_routes"],
        "hit_rate": pc["hit_rate"],
        "hit_tokens": pc["hit_tokens"],
        "per_replica": per_replica,
    }


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests, same assertions)")
    ap.add_argument("--out", default="BENCH_prefix.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    arch = args.arch + ("" if args.arch.endswith("-smoke") else "-smoke")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(args.seed), cfg)
    n = 8 if args.smoke else 24
    geometry = dict(slots=4, max_len=128, buckets=(16, 32, 64, 128),
                    cache_bytes=64 << 20)

    print(f"\narch={arch} requests={n} (shared-prefix) "
          f"geometry slots=4 max_len=128")
    shared = bench_scenario(
        "shared-prefix", cfg, params,
        _shared_prefix_stream(cfg.vocab_size, requests=n, prefix_len=48,
                              tail_lo=4, tail_hi=12, max_new=6,
                              seed=args.seed),
        **geometry)
    multi = bench_scenario(
        "multi-turn", cfg, params,
        _multi_turn_stream(cfg.vocab_size, sessions=2 if args.smoke else 4,
                           turns=4, turn_len=10, max_new=4,
                           seed=args.seed + 1),
        **geometry)
    fleet = bench_fleet(cfg, params, smoke=args.smoke, seed=args.seed)

    # the headline claim, asserted: prefix reuse at least halves prefill
    # compute on the canonical shared-system-prompt workload
    assert shared["prefill_reduction"] >= 2.0, (
        f"expected >= 2x prefill-compute reduction, got "
        f"{shared['prefill_reduction']}x")
    assert multi["prefill_reduction"] >= 2.0, (
        f"multi-turn reduction {multi['prefill_reduction']}x < 2x")

    payload = {
        "benchmark": "prefix_reuse",
        "arch": arch,
        "requests": n,
        "prefill_reduction": shared["prefill_reduction"],
        "scenarios": {"shared_prefix": shared, "multi_turn": multi},
        "fleet": fleet,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nheadline: {shared['prefill_reduction']:.2f}x prefill-compute "
          f"reduction at exact token parity")
    print(f"wrote {args.out}")
    print("prefix_reuse OK")


if __name__ == "__main__":
    main()
