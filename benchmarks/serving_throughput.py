"""Serving data-plane benchmark: fused single-program step vs legacy host loop.

Measures, for the same synthetic request stream on one model:

  * tok/s (end-to-end, including admission)
  * host<->device syncs per decode step — the fused data plane performs
    EXACTLY 1 blocking sync per step (a single packed "tokens|active|done"
    fetch); the legacy loop pays ~2 per active slot (one device_get per
    sampled token + one length sync) plus per-slot sample dispatches.
  * prefill program calls — batched admission runs one program per prompt
    bucket instead of one per request.

Writes machine-readable results to ``BENCH_serving.json`` (``--out``) so the
perf trajectory is tracked across PRs.

    PYTHONPATH=src python benchmarks/serving_throughput.py \
        [--arch qwen2-0.5b] [--requests 16] [--max-new 16]
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


def _request_stream(cfg, requests: int, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(requests):
        plen = int(rng.integers(6, 30))
        if cfg.frontend == "audio":
            prompt = rng.integers(0, cfg.vocab_size,
                                  (cfg.num_codebooks, plen), dtype=np.int32)
        else:
            prompt = rng.integers(0, cfg.vocab_size, (plen,), dtype=np.int32)
        out.append(Request(request_id=i, prompt=prompt, max_new_tokens=max_new,
                           sampling=SamplingConfig()))
    return out


def bench_mode(cfg, params, reqs, *, fused: bool, slots: int, max_len: int,
               sync_every: int = 1) -> dict:
    engine = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                           prompt_buckets=(16, 32, 64), fused=fused,
                           sync_every=sync_every)
    engine.warmup()  # steady-state measurement: all programs compiled
    warm_stats = dict(engine.stats)

    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    results = engine.run_to_completion()
    wall = time.perf_counter() - t0

    tokens = sum(len(r.tokens) for r in results.values())
    decode_steps = engine.stats["decode_steps"] - warm_stats["decode_steps"]
    decode_syncs = engine.stats["host_syncs_decode"] - warm_stats["host_syncs_decode"]
    prefill_calls = engine.stats["prefill_calls"] - warm_stats["prefill_calls"]
    return {
        "mode": ("fused" if fused else "legacy")
                + (f"(sync_every={sync_every})" if sync_every > 1 else ""),
        "tokens": tokens,
        "wall_s": wall,
        "tok_s": tokens / max(wall, 1e-9),
        "decode_steps": decode_steps,
        "decode_syncs": decode_syncs,
        "syncs_per_step": decode_syncs / max(decode_steps, 1),
        "prefill_calls": prefill_calls,
        "results": {rid: r.tokens for rid, r in results.items()},
    }


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--sync-every", type=int, default=4,
                    help="extra fused run with k-step sync batching")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()

    arch = args.arch + ("" if args.arch.endswith("-smoke") else "-smoke")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(0), cfg)
    reqs = _request_stream(cfg, args.requests, args.max_new)

    rows = [
        bench_mode(cfg, params, reqs, fused=False, slots=args.slots,
                   max_len=args.max_len),
        bench_mode(cfg, params, reqs, fused=True, slots=args.slots,
                   max_len=args.max_len),
    ]
    if args.sync_every > 1:
        rows.append(bench_mode(cfg, params, reqs, fused=True, slots=args.slots,
                               max_len=args.max_len, sync_every=args.sync_every))

    print(f"\narch={arch} requests={args.requests} max_new={args.max_new} "
          f"slots={args.slots}")
    hdr = (f"{'mode':<20} {'tok/s':>8} {'wall_s':>7} {'steps':>6} "
           f"{'syncs':>6} {'syncs/step':>10} {'prefill_calls':>13}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['mode']:<20} {r['tok_s']:>8.1f} {r['wall_s']:>7.2f} "
              f"{r['decode_steps']:>6} {r['decode_syncs']:>6} "
              f"{r['syncs_per_step']:>10.2f} {r['prefill_calls']:>13}")

    legacy, fused = rows[0], rows[1]
    speedup = fused["tok_s"] / max(legacy["tok_s"], 1e-9)
    print(f"\nfused speedup: {speedup:.2f}x tok/s | syncs/step "
          f"{legacy['syncs_per_step']:.2f} -> {fused['syncs_per_step']:.2f}")
    # greedy decode: the refactor must not change a single served token
    assert fused["results"] == legacy["results"], "token parity broken"
    assert fused["syncs_per_step"] == 1.0, (
        f"fused data plane must sync exactly once per decode step, "
        f"got {fused['syncs_per_step']}")
    assert fused["tok_s"] > legacy["tok_s"], "fused engine should be faster"

    payload = {
        "benchmark": "serving_throughput",
        "arch": arch,
        "requests": args.requests,
        "max_new": args.max_new,
        "slots": args.slots,
        "fused_speedup": round(speedup, 3),
        "modes": [{k: v for k, v in r.items() if k != "results"}
                  for r in rows],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("serving_throughput OK")


if __name__ == "__main__":
    main()
