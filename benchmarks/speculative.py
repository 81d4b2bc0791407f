"""Speculative decoding benchmark: fused draft-verify data plane vs the
plain fused decode loop (the PR-1 baseline).

Same request stream, same model, greedy decoding, both engines warm:

  * tok/s end-to-end (admission + decode) — the headline; the stream is
    decode-dominated, so this is decode throughput to first order.
  * TPOT (per-output-token decode latency, p50 over requests) — the latency
    face of the same coin (satellite telemetry).
  * acceptance rate + tokens per slot-step — WHY it is faster: one target
    forward emits up to k+1 tokens when the proposer's drafts survive
    lossless rejection sampling.
  * token parity — greedy streams must be BYTE-IDENTICAL: speculative
    decoding is an acceleration, never a behavior change (asserted).

The NGram (prompt-lookup) proposer drafts from the request's own emitted
history, so the benchmark asserts on the same workload class it targets:
continuations with internal repetition. ``--smoke`` is the CI variant — a
2-slot engine that asserts a NONZERO acceptance rate and token parity
without gating on wall-clock.

Writes machine-readable results to ``BENCH_spec.json`` (``--out``), gated by
``benchmarks/validate_bench.py`` (speedup and acceptance-rate ratios are
hard <=20%-regression gates; absolute tok/s is advisory, as everywhere).

    PYTHONPATH=src python benchmarks/speculative.py [--arch qwen2-0.5b]
        [--requests 16] [--max-new 32] [--k 4] [--smoke]
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
from repro.serving.sampling import SamplingConfig
from repro.serving.speculative import SpecConfig


def _request_stream(cfg, requests: int, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(requests):
        plen = int(rng.integers(6, 30))
        prompt = rng.integers(0, cfg.vocab_size, (plen,), dtype=np.int32)
        out.append(Request(request_id=i, prompt=prompt, max_new_tokens=max_new,
                           sampling=SamplingConfig()))
    return out


def bench_mode(cfg, params, reqs, *, spec: SpecConfig | None, slots: int,
               max_len: int, repeats: int = 3) -> dict:
    """Serve the stream ``repeats`` times on fresh warm engines and keep the
    fastest trial — wall-clock on a shared CI runner is noisy, and the
    best-of-N trial is the least-contended measurement of the same
    deterministic work (token streams are identical across trials, which is
    also asserted)."""
    best = None
    for _ in range(max(repeats, 1)):
        engine = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                               prompt_buckets=(16, 32, 64), spec=spec)
        engine.warmup()
        warm = dict(engine.stats)

        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        results = engine.run_to_completion()
        wall = time.perf_counter() - t0

        tokens = sum(len(r.tokens) for r in results.values())
        lat = engine.latency_summary()
        row = {
            "mode": "baseline-fused" if spec is None
                    else f"spec({spec.proposer} k={spec.k})",
            "tokens": tokens,
            "wall_s": wall,
            "tok_s": tokens / max(wall, 1e-9),
            "decode_steps": engine.stats["decode_steps"] - warm["decode_steps"],
            "tpot_p50_s": lat["tpot_p50_s"],
            "tpot_p95_s": lat["tpot_p95_s"],
            "ttft_p50_s": lat["ttft_p50_s"],
            "results": {rid: r.tokens for rid, r in results.items()},
        }
        if spec is not None:
            row.update(engine.spec_summary())
        if best is not None:
            assert row["results"] == best["results"], (
                "greedy token streams differ across trials")
        if best is None or row["tok_s"] > best["tok_s"]:
            best = row
    return best


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=3,
                    help="trials per mode; the fastest is kept")
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: 2 slots, tiny stream, asserts nonzero "
                         "acceptance + parity (no wall-clock gate)")
    ap.add_argument("--out", default="BENCH_spec.json")
    args = ap.parse_args()

    if args.smoke:
        args.requests, args.max_new, args.slots = 6, 12, 2
        args.max_len = min(args.max_len, 64)
        args.repeats = 1

    arch = args.arch + ("" if args.arch.endswith("-smoke") else "-smoke")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(0), cfg)
    reqs = _request_stream(cfg, args.requests, args.max_new)
    spec = SpecConfig(k=args.k, proposer="ngram")

    base = bench_mode(cfg, params, reqs, spec=None, slots=args.slots,
                      max_len=args.max_len, repeats=args.repeats)
    fast = bench_mode(cfg, params, reqs, spec=spec, slots=args.slots,
                      max_len=args.max_len, repeats=args.repeats)

    speedup = fast["tok_s"] / max(base["tok_s"], 1e-9)
    step_ratio = base["decode_steps"] / max(fast["decode_steps"], 1)
    print(f"\narch={arch} requests={args.requests} max_new={args.max_new} "
          f"slots={args.slots} k={args.k}")
    hdr = (f"{'mode':<18} {'tok/s':>8} {'wall_s':>7} {'steps':>6} "
           f"{'tpot_p50':>9} {'accept':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in (base, fast):
        acc = f"{r['acceptance_rate']:.0%}" if "acceptance_rate" in r else "--"
        print(f"{r['mode']:<18} {r['tok_s']:>8.1f} {r['wall_s']:>7.2f} "
              f"{r['decode_steps']:>6} {r['tpot_p50_s'] * 1e3:>8.2f}m {acc:>7}")
    print(f"\nspeculative speedup: {speedup:.2f}x tok/s | "
          f"{step_ratio:.2f}x fewer target steps | acceptance "
          f"{fast['acceptance_rate']:.0%}")

    # lossless contract: greedy streams byte-identical, always asserted
    assert fast["results"] == base["results"], (
        "speculative decoding changed a greedy token stream")
    assert fast["acceptance_rate"] > 0, "proposer never had a draft accepted"
    if not args.smoke:
        assert speedup >= 1.5, (
            f"speculative decode speedup {speedup:.2f}x < 1.5x headline")

    payload = {
        "benchmark": "speculative",
        "arch": arch,
        "requests": args.requests,
        "max_new": args.max_new,
        "slots": args.slots,
        "k": args.k,
        "proposer": "ngram",
        "speedup": round(speedup, 3),
        "step_reduction": round(step_ratio, 3),
        "acceptance_rate": fast["acceptance_rate"],
        "tokens_per_slot_step": fast["tokens_per_slot_step"],
        "token_parity": fast["results"] == base["results"],
        "modes": [{k: v for k, v in r.items() if k != "results"}
                  for r in (base, fast)],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("speculative OK")


if __name__ == "__main__":
    main()
