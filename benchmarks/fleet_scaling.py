"""Fleet elasticity benchmark: SLO-driven autoscaling vs static allocation.

Runs the SAME seeded bursty trace through three allocation policies on the
same cluster (serving replicas coexisting with preemptible BATCH training
jobs), entirely in virtual time, and compares:

  * **autoscaled**    — min..max replicas, queue/p95-driven scale-up (with
                        BATCH preemption + FTManager checkpoint-requeue when
                        the cluster is full), idle-driven scale-to-min.
  * **static-minimal** — the scale-to-min footprint held for the whole run:
                        cheapest chips, worst burst latency.
  * **static-peak**   — the burst footprint held for the whole run: best
                        latency, most chip-seconds (and starved batch jobs).

The paper's claim under test: an elastic lease-based fleet beats static-min
on p99 latency while consuming fewer chip-seconds than static-peak.
Deterministic given --seed; writes machine-readable results to
``BENCH_fleet.json`` so the trajectory is tracked across PRs.

    PYTHONPATH=src python benchmarks/fleet_scaling.py [--smoke] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro import configs
from repro.core import recompile
from repro.fleet import (SLO, FleetConfig, FleetManager, bursty_trace,
                         materialize)
from repro.models import transformer


def scenario_table(smoke: bool) -> dict:
    """Cluster + trace geometry. Smoke = the CI variant: 2 replicas max,
    one batch job, a short burst — still must exhibit scale-up, scale-down,
    and at least one preemption."""
    if smoke:
        return dict(
            chips=2, min_replicas=1, max_replicas=2,
            batch_jobs=[(1, 20)],
            trace=dict(duration_s=16.0, base_rate=0.3, burst_rate=6.0,
                       bursts=((3.0, 9.0),), prompt_median=8, prompt_lo=4,
                       prompt_hi=16, max_new_lo=4, max_new_hi=6),
        )
    return dict(
        chips=4, min_replicas=1, max_replicas=4,
        batch_jobs=[(1, 30), (1, 30)],
        trace=dict(duration_s=24.0, base_rate=0.3, burst_rate=8.0,
                   bursts=((4.0, 12.0),), prompt_median=8, prompt_lo=4,
                   prompt_hi=16, max_new_lo=4, max_new_hi=8),
    )


def run_policy(name: str, cfg, params, reqs, *, chips, min_replicas,
               max_replicas, batch_jobs, seed: int) -> dict:
    fleet_cfg = FleetConfig(
        min_replicas=min_replicas, max_replicas=max_replicas,
        slots=2, max_len=64, prompt_buckets=(8, 16), tick_s=0.1,
        warm_boot_s=0.5, cold_boot_s=1.5, settle_s=30.0)
    slo = SLO(p95_target_s=1.5, queue_high_per_slot=1.0, up_cooldown_s=1.0,
              down_cooldown_s=2.0, idle_drain_s=3.0)
    fm = FleetManager.build(cfg, params, chips=chips, fleet=fleet_cfg,
                            slo=slo, batch_jobs=batch_jobs)
    # every policy is accounted over the SAME virtual window (trace duration
    # + a fixed tail), so chip-second totals are directly comparable
    horizon = max(r.arrival_s for r in reqs) + 12.0
    t0 = time.perf_counter()
    report = fm.run_trace(reqs, until_s=horizon)
    wall = time.perf_counter() - t0
    assert report.served == report.requests, (
        f"{name}: {report.served}/{report.requests} served")
    assert report.reconciled, f"{name}: per-tenant ledger does not reconcile"
    out = report.to_dict()
    out["policy"] = name
    out["real_wall_s"] = round(wall, 2)
    return out


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: tiny trace, 2 replicas max")
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args()

    arch = args.arch + ("" if args.arch.endswith("-smoke") else "-smoke")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(args.seed), cfg)
    spec = scenario_table(args.smoke)
    trace = bursty_trace(seed=args.seed, **spec["trace"])
    reqs = materialize(trace, vocab_size=cfg.vocab_size, seed=args.seed + 1)
    print(f"arch={arch} trace={len(reqs)} requests "
          f"(burst {spec['trace']['burst_rate']}/s) chips={spec['chips']} "
          f"batch_jobs={len(spec['batch_jobs'])}")

    mx = spec["max_replicas"]
    rows = [
        run_policy("autoscaled", cfg, params, reqs, chips=spec["chips"],
                   min_replicas=spec["min_replicas"], max_replicas=mx,
                   batch_jobs=spec["batch_jobs"], seed=args.seed),
        run_policy("static-minimal", cfg, params, reqs, chips=spec["chips"],
                   min_replicas=spec["min_replicas"],
                   max_replicas=spec["min_replicas"],
                   batch_jobs=spec["batch_jobs"], seed=args.seed),
        run_policy("static-peak", cfg, params, reqs, chips=spec["chips"],
                   min_replicas=mx, max_replicas=mx,
                   batch_jobs=spec["batch_jobs"], seed=args.seed),
    ]

    hdr = (f"{'policy':<15} {'p50_s':>7} {'p99_s':>7} {'tok/s':>7} "
           f"{'chip_s':>7} {'ups':>4} {'downs':>6} {'preempt':>8}")
    print("\n" + hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['policy']:<15} {r['latency_p50_s']:>7.2f} "
              f"{r['latency_p99_s']:>7.2f} {r['tokens_per_s']:>7.1f} "
              f"{r['serving_chip_s']:>7.1f} {r['scale_ups']:>4} "
              f"{r['scale_downs']:>6} {r['preemptions']:>8}")

    auto, smin, speak = rows
    # ---- the paper's elasticity claim, asserted ----
    assert auto["latency_p99_s"] < smin["latency_p99_s"], (
        f"autoscaled p99 {auto['latency_p99_s']:.2f}s must beat static-min "
        f"{smin['latency_p99_s']:.2f}s under the bursty trace")
    assert auto["serving_chip_s"] < speak["serving_chip_s"], (
        f"autoscaled {auto['serving_chip_s']:.1f} chip-s must undercut "
        f"static-peak {speak['serving_chip_s']:.1f}")
    assert auto["preemptions"] >= 1 and auto["batch"]["checkpoints"] >= 1, (
        "scale-up must preempt (and checkpoint) at least one BATCH job")
    assert auto["batch"]["resumes"] >= 1, (
        "a preempted BATCH job must requeue and resume from its checkpoint")
    assert auto["scale_ups"] >= 1 and auto["lease_releases"] >= 1, (
        "autoscaled run must both scale up and release a lease (scale-to-min)")
    print(f"\nautoscaled: p99 {auto['latency_p99_s']:.2f}s "
          f"(static-min {smin['latency_p99_s']:.2f}s, "
          f"{smin['latency_p99_s'] / max(auto['latency_p99_s'], 1e-9):.1f}x worse) | "
          f"chip-s {auto['serving_chip_s']:.1f} "
          f"(static-peak {speak['serving_chip_s']:.1f}, "
          f"{speak['serving_chip_s'] / max(auto['serving_chip_s'], 1e-9):.2f}x more) | "
          f"preemptions {auto['preemptions']} resumes {auto['batch']['resumes']}")

    payload = {
        "benchmark": "fleet_scaling",
        "arch": arch,
        "seed": args.seed,
        "smoke": args.smoke,
        "trace": {**spec["trace"], "bursts": [list(b) for b in spec["trace"]["bursts"]],
                  "requests": len(reqs)},
        "scenarios": {r["policy"]: r for r in rows},
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("fleet_scaling OK")


if __name__ == "__main__":
    main()
