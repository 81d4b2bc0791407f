"""Disaggregated prefill/decode fleet benchmark: phase-specialized pools
with KV page handoff vs a monolithic fleet, at matched replica footprint.

Runs the SAME seeded bursty trace (long prompts inside the burst — the
prefill-heavy regime the split targets) through two topologies over the
same virtual-time window:

  * **monolithic** — every replica runs both phases; chunked prefill uses
    a small chunk cap to protect co-resident decode TPOT, which is exactly
    what throttles prompt admission under the burst.
  * **disagg**     — a prefill pool (full-width chunks, wide admission
    batches: no co-resident decode to protect) computes prompts and ships
    KV pages over the :class:`~repro.fleet.disagg.KVHandoff` plane to a
    decode pool, each pool autoscaled against its own SLO (TTFT vs TPOT).

The paper's converged-infrastructure claim under test: specializing
execution per phase (while keeping one lease/container abstraction) cuts
burst TTFT p99 by >= 1.3x at <= 1.05x the chip-seconds, with greedy token
streams byte-identical to the monolithic fleet. Deterministic given
--seed; writes ``BENCH_disagg.json`` for the CI regression gate.

    PYTHONPATH=src python benchmarks/disagg.py [--smoke] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro import configs
from repro.core import recompile
from repro.fleet import (SLO, DisaggConfig, DisaggFleetManager, FleetConfig,
                         FleetManager, bursty_trace, materialize)
from repro.models import transformer

TTFT_RATIO_FLOOR = 1.3   # disagg burst TTFT p99 must beat mono by this
CHIP_RATIO_CEIL = 1.05   # ...without spending more than 5% extra chip-s


def scenario_table(smoke: bool) -> dict:
    """Trace + fleet geometry. Both topologies get the same max footprint
    (mono 2..4 replicas vs disagg 1+1..2+2). Smoke = the CI variant: a
    shorter burst, same shape — still must hand off and show the pools
    scaling independently."""
    trace = dict(duration_s=16.0, base_rate=0.4, burst_rate=8.0,
                 bursts=((3.0, 11.0),), prompt_median=8, prompt_lo=4,
                 prompt_hi=32, max_new_lo=6, max_new_hi=10,
                 burst_prompt_median=28)
    if smoke:
        trace.update(duration_s=10.0, bursts=((2.0, 8.0),))
    return dict(
        chips=8, mono_min=2, mono_max=4,
        disagg=DisaggConfig(
            prefill_min=1, prefill_max=2, decode_min=1, decode_max=2,
            # prefill engines admit wide: there is no decode latency to
            # protect, so the batch dimension is free admission throughput
            prefill_slots=4,
            decode_slo=SLO(p95_target_s=0.3, queue_high_per_slot=3.0)),
        trace=trace)


def _fleet_cfg(min_replicas: int, max_replicas: int) -> FleetConfig:
    # prefill_chunk_tokens=8 is the monolithic fleet's TPOT-protective
    # chunk cap — the disagg prefill pool overrides it to full width
    return FleetConfig(
        min_replicas=min_replicas, max_replicas=max_replicas, slots=2,
        max_len=48, prompt_buckets=(8, 16, 32), tick_s=0.05, page_size=8,
        prefix_cache_mb=1.0, warm_boot_s=0.4, cold_boot_s=0.8,
        prefill_chunk_tokens=8)


def run_topology(name: str, cfg, params, reqs, spec, *, horizon: float) -> tuple:
    if name == "monolithic":
        fm = FleetManager.build(cfg, params, chips=spec["chips"],
                                fleet=_fleet_cfg(spec["mono_min"],
                                                 spec["mono_max"]))
    else:
        d = spec["disagg"]
        fm = DisaggFleetManager.build(
            cfg, params, chips=spec["chips"],
            fleet=_fleet_cfg(d.prefill_min + d.decode_min,
                             d.prefill_max + d.decode_max),
            disagg=d)
    t0 = time.perf_counter()
    report = fm.run_trace(reqs, until_s=horizon)
    wall = time.perf_counter() - t0
    assert report.served == report.requests, (
        f"{name}: {report.served}/{report.requests} served")
    assert report.reconciled, f"{name}: per-tenant ledger does not reconcile"
    row = report.to_dict()
    row["topology"] = name
    row["real_wall_s"] = round(wall, 2)
    return fm, row


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: short burst, handoff + per-pool "
                         "scaling asserted, no ratio gates")
    ap.add_argument("--out", default="BENCH_disagg.json")
    args = ap.parse_args()

    arch = args.arch + ("" if args.arch.endswith("-smoke") else "-smoke")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(args.seed), cfg)
    spec = scenario_table(args.smoke)
    trace = bursty_trace(seed=args.seed, **spec["trace"])
    reqs = materialize(trace, vocab_size=cfg.vocab_size, seed=args.seed + 1,
                       max_prompt_len=32)
    # both topologies are accounted over the SAME virtual window, so
    # chip-second totals are directly comparable
    horizon = max(r.arrival_s for r in reqs) + 12.0
    print(f"arch={arch} trace={len(reqs)} requests "
          f"(burst {spec['trace']['burst_rate']}/s, "
          f"burst prompts ~{spec['trace']['burst_prompt_median']} tok) "
          f"chips={spec['chips']} horizon={horizon:.1f}s")

    mono_fm, mono = run_topology("monolithic", cfg, params, reqs, spec,
                                 horizon=horizon)
    d_fm, dis = run_topology("disagg", cfg, params, reqs, spec,
                             horizon=horizon)

    hdr = (f"{'topology':<12} {'ttft_p50':>9} {'ttft_p99':>9} {'p99_s':>7} "
           f"{'chip_s':>7} {'handoffs':>9} {'fallbacks':>10}")
    print("\n" + hdr)
    print("-" * len(hdr))
    for r in (mono, dis):
        h = r["disagg"].get("handoff", {})
        print(f"{r['topology']:<12} {r['ttft_virtual_p50_s']:>9.3f} "
              f"{r['ttft_virtual_p99_s']:>9.3f} {r['latency_p99_s']:>7.2f} "
              f"{r['serving_chip_s']:>7.1f} {h.get('installed', 0):>9} "
              f"{r['disagg'].get('fallback_submits', 0):>10}")

    # ---- byte parity: the split must not change a single token ----
    sm, sd = mono_fm.token_streams(), d_fm.token_streams()
    assert set(sm) == set(sd) == {r.request_id for r in reqs}
    mismatched = [rid for rid in sm if sm[rid] != sd[rid]]
    assert not mismatched, f"{len(mismatched)} streams diverged: " \
                           f"{sorted(mismatched)[:5]}"
    parity = True

    handoff = dis["disagg"]["handoff"]
    pools = dis["disagg"]["pools"]
    assert handoff["installed"] >= 1, "disagg run never handed off KV pages"
    assert handoff["sha_rejected"] == 0, "unexpected sha rejects"
    # per-pool autoscaling independence: at least one pool reacted to the
    # burst while the other held its own floor — one global cooldown/window
    # could not produce this
    scale_ups = {p: pools[p]["scale_ups"] for p in ("prefill", "decode")}
    assert sum(scale_ups.values()) >= 1, "neither pool ever scaled up"
    assert any(pools[p]["live"] == pools[p]["min"]
               for p in ("prefill", "decode")), \
        "no pool settled back to its own floor"

    ttft_ratio = (mono["ttft_virtual_p99_s"]
                  / max(dis["ttft_virtual_p99_s"], 1e-9))
    chip_ratio = dis["serving_chip_s"] / max(mono["serving_chip_s"], 1e-9)
    print(f"\ndisagg: TTFT p99 {dis['ttft_virtual_p99_s']:.3f}s vs mono "
          f"{mono['ttft_virtual_p99_s']:.3f}s ({ttft_ratio:.2f}x better) | "
          f"chip-s {dis['serving_chip_s']:.1f} vs {mono['serving_chip_s']:.1f} "
          f"({chip_ratio:.2f}x) | {handoff['installed']} handoffs "
          f"({handoff['bytes'] / 1e6:.1f} MB) | pool scale-ups {scale_ups}")

    if not args.smoke:
        # ---- the headline claim, asserted ----
        assert ttft_ratio >= TTFT_RATIO_FLOOR, (
            f"disagg TTFT p99 must be >= {TTFT_RATIO_FLOOR}x better under "
            f"the prefill-heavy burst (got {ttft_ratio:.2f}x)")
        assert chip_ratio <= CHIP_RATIO_CEIL, (
            f"disagg chip-seconds must stay within {CHIP_RATIO_CEIL}x of "
            f"monolithic (got {chip_ratio:.2f}x)")

    payload = {
        "benchmark": "disagg",
        "arch": arch,
        "seed": args.seed,
        "smoke": args.smoke,
        "trace": {**spec["trace"],
                  "bursts": [list(b) for b in spec["trace"]["bursts"]],
                  "requests": len(reqs), "horizon_s": horizon},
        "headline": {
            "ttft_p99_ratio": round(ttft_ratio, 4),
            "chip_seconds_ratio": round(chip_ratio, 4),
            "token_parity": parity,
            "handoffs_installed": handoff["installed"],
        },
        "scenarios": {r["topology"]: r for r in (mono, dis)},
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("disagg OK")


if __name__ == "__main__":
    main()
