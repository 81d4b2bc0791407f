"""Paged-KV benchmark: the slots x memory frontier of the block-managed
engine vs the contiguous slot engine (the PR-1..5 baseline).

Same request stream, same model, greedy decoding, both engines warm:

  * peak concurrent requests per byte of KV memory — the headline. The slot
    engine must provision ``slots x max_len`` cache strips to hold ``slots``
    requests; the paged engine holds the same concurrency in a pool sized by
    TOKENS ACTUALLY HELD (pages_for(prompt+decode) per request), so short
    requests against a long-context provisioning stop paying for max_len.
  * decode throughput (tok/s end-to-end) — the cost side: gather/scatter
    through block tables must stay within 10% of the contiguous layout.
  * token parity — greedy streams must be BYTE-IDENTICAL between the two
    engines: paging is a memory-management change, never a behavior change
    (asserted, request by request).

``--smoke`` is the CI variant: a 16-slot engine over 24 requests that must
sustain MORE THAN 8 requests in flight simultaneously at token parity,
without gating on wall-clock. The full run drives 128 concurrent requests
through ONE replica and asserts the >=2x concurrency-per-KV-byte headline
plus the <=10% decode-throughput bound.

Writes machine-readable results to ``BENCH_paged.json`` (``--out``), gated
by ``benchmarks/validate_bench.py`` (the concurrency-per-byte ratio and
decode tok/s ratio are hard <=20%-regression gates; absolute tok/s is
advisory, as everywhere).

    PYTHONPATH=src python benchmarks/paged_kv.py [--arch qwen2-0.5b]
        [--concurrency 128] [--requests 144] [--max-new 10] [--smoke]
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
from repro.serving.block_manager import pages_for
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import SamplingConfig

PAGE = 8


def _request_stream(cfg, requests: int, max_new: int, seed: int = 0,
                    shared_prefix: int = 6):
    """Short prompts (some sharing a system prefix) against a long-context
    engine — the workload class where paging wins: the slot engine pays for
    max_len per request, the paged engine for actual tokens."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, shared_prefix,
                              dtype=np.int32)
    out = []
    for i in range(requests):
        plen = int(rng.integers(4, 18))
        prompt = rng.integers(0, cfg.vocab_size, (plen,), dtype=np.int32)
        if i % 2 == 0:
            prompt = np.concatenate([sys_prompt, prompt])
        out.append(Request(request_id=i, prompt=prompt,
                           max_new_tokens=max_new,
                           sampling=SamplingConfig()))
    return out


def _serve_tracked(engine, reqs):
    """run_to_completion with peak-concurrency tracking."""
    for r in reqs:
        engine.submit(r)
    peak = 0
    t0 = time.perf_counter()
    while True:
        active = engine.step()
        peak = max(peak, active)
        if active == 0 and not engine.queue:
            break
    wall = time.perf_counter() - t0
    return engine.results, peak, wall


def bench_mode(cfg, params, reqs, *, slots: int, max_len: int,
               page_size: int | None, kv_pages: int | None,
               repeats: int = 3) -> dict:
    """Serve the stream ``repeats`` times on fresh warm engines and keep the
    fastest trial (token streams are identical across trials — asserted)."""
    best = None
    for _ in range(max(repeats, 1)):
        engine = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                               prompt_buckets=(16, 32, max_len),
                               page_size=page_size, kv_pages=kv_pages,
                               prefix_cache_bytes=None)
        engine.warmup()
        results, peak, wall = _serve_tracked(engine, reqs)
        tokens = sum(len(r.tokens) for r in results.values())
        if page_size is not None:
            # pool bytes actually provisioned (null page excluded)
            kv_bytes = (engine.kv_pages - 1) * engine.page_bytes
            token_bytes = engine.page_bytes // page_size
        else:
            # contiguous strips: slots x max_len tokens, same per-token cost
            probe = transformer.init_paged_states(
                cfg, 2, PAGE, jax.numpy.dtype(cfg.activ_dtype))
            token_bytes = sum(
                int(np.prod(l.shape)) // 2 * jax.numpy.dtype(l.dtype).itemsize
                for l in jax.tree.leaves(probe)) // PAGE
            kv_bytes = slots * max_len * token_bytes
        lat = engine.latency_summary()
        row = {
            "mode": ("slot-engine" if page_size is None
                     else f"paged(page={page_size})"),
            "slots": slots,
            "kv_bytes": int(kv_bytes),
            "kv_tokens_capacity": int(kv_bytes // token_bytes),
            "peak_concurrent": peak,
            "tokens": tokens,
            "wall_s": wall,
            "tok_s": tokens / max(wall, 1e-9),
            "decode_steps": engine.stats["decode_steps"],
            "ttft_p50_s": lat["ttft_p50_s"],
            "tpot_p50_s": lat["tpot_p50_s"],
            "preemptions": engine.stats["preemptions"],
            "results": {rid: r.tokens for rid, r in results.items()},
        }
        if page_size is not None:
            row["paged"] = engine.paged_summary()
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
    ap.add_argument("--concurrency", type=int, default=128,
                    help="engine slots = target in-flight requests")
    ap.add_argument("--requests", type=int, default=144)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--max-len", type=int, default=128,
                    help="provisioned context per request (the slot engine "
                         "pays for all of it; the paged engine only for "
                         "pages actually written)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="trials per mode; the fastest is kept")
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: 16 slots, 24 requests, asserts >8 "
                         "peak concurrency + parity (no wall-clock gate)")
    ap.add_argument("--out", default="BENCH_paged.json")
    args = ap.parse_args()

    if args.smoke:
        args.concurrency, args.requests, args.max_new = 16, 24, 8
        args.max_len = min(args.max_len, 64)
        args.repeats = 1

    arch = args.arch + ("" if args.arch.endswith("-smoke") else "-smoke")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(0), cfg)
    reqs = _request_stream(cfg, args.requests, args.max_new)

    # provision the pool for the worst-case resident set: the `slots`
    # hungriest requests fully grown, plus growth headroom — zero
    # preemptions, so the throughput comparison isolates the data-plane cost
    need = sorted((pages_for(int(np.asarray(r.prompt).shape[-1])
                             + r.max_new_tokens, PAGE) for r in reqs),
                  reverse=True)
    kv_pages = sum(need[:args.concurrency]) + args.concurrency // 8 + 1

    base = bench_mode(cfg, params, reqs, slots=args.concurrency,
                      max_len=args.max_len, page_size=None, kv_pages=None,
                      repeats=args.repeats)
    paged = bench_mode(cfg, params, reqs, slots=args.concurrency,
                       max_len=args.max_len, page_size=PAGE,
                       kv_pages=kv_pages, repeats=args.repeats)

    parity = paged["results"] == base["results"]
    tok_s_ratio = paged["tok_s"] / max(base["tok_s"], 1e-9)
    # requests-in-flight each engine sustains per byte of provisioned KV
    conc_per_byte_ratio = (
        (paged["peak_concurrent"] / paged["kv_bytes"])
        / max(base["peak_concurrent"] / base["kv_bytes"], 1e-12))

    print(f"\narch={arch} concurrency={args.concurrency} "
          f"requests={args.requests} max_new={args.max_new} "
          f"max_len={args.max_len} page={PAGE}")
    hdr = (f"{'mode':<16} {'peak':>5} {'KV MiB':>8} {'cap tok':>8} "
           f"{'tok/s':>8} {'steps':>6} {'preempt':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in (base, paged):
        print(f"{r['mode']:<16} {r['peak_concurrent']:>5} "
              f"{r['kv_bytes'] / (1 << 20):>8.2f} "
              f"{r['kv_tokens_capacity']:>8} {r['tok_s']:>8.1f} "
              f"{r['decode_steps']:>6} {r['preemptions']:>7}")
    print(f"\nconcurrency per KV byte: {conc_per_byte_ratio:.2f}x | "
          f"decode throughput ratio: {tok_s_ratio:.2f}x | "
          f"token parity: {parity}")

    # paging is a memory-management change, never a behavior change
    assert parity, "paged engine changed a greedy token stream"
    if args.smoke:
        assert paged["peak_concurrent"] > 8, (
            f"paged smoke sustained only {paged['peak_concurrent']} "
            f"concurrent requests (need > 8)")
    else:
        assert paged["peak_concurrent"] >= args.concurrency, (
            f"paged engine never reached {args.concurrency} concurrent "
            f"requests (peak {paged['peak_concurrent']})")
        assert conc_per_byte_ratio >= 2.0, (
            f"concurrency-per-KV-byte {conc_per_byte_ratio:.2f}x < 2x "
            f"headline")
        assert tok_s_ratio >= 0.9, (
            f"paged decode throughput {tok_s_ratio:.2f}x of contiguous "
            f"(> 10% regression)")

    payload = {
        "benchmark": "paged_kv",
        "arch": arch,
        "concurrency": args.concurrency,
        "requests": args.requests,
        "max_new": args.max_new,
        "max_len": args.max_len,
        "page_size": PAGE,
        "kv_pages": kv_pages,
        "concurrency_per_kv_byte": round(conc_per_byte_ratio, 3),
        "kv_bytes_reduction": round(base["kv_bytes"] / paged["kv_bytes"], 3),
        "decode_tok_s_ratio": round(tok_s_ratio, 3),
        "token_parity": parity,
        "modes": [{k: v for k, v in r.items() if k != "results"}
                  for r in (base, paged)],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("paged_kv OK")


if __name__ == "__main__":
    main()
