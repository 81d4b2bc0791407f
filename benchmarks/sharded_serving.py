"""Sharded serving benchmark: tensor/expert-parallel replicas vs one chip.

Three claims, all asserted here and re-gated by ``validate_bench.py`` on the
committed ``BENCH_sharding.json`` (docs/sharding.md):

  * **capacity** — a config whose per-replica footprint (params + KV pool)
    exceeds one chip's modeled HBM *fits* at TP=2: per-chip bytes halve
    along the model axis, and the fleet's width-vs-count policy records
    that it was FORCED to widen ("widened past 1x1 ...").
  * **parity** — greedy token streams from a (1,2)-mesh replica are
    byte-identical to the single-device engine, on both the fused-decode
    and the paged+chunked-prefill data planes: sharding is a capacity/
    latency tool, never a behavior change.
  * **efficiency** — per-chip-second throughput at TP=2 stays within 20%
    of the 1-chip engine. Modeled at the profile roofline from the ledger's
    billed FLOPs (the compiled artifact's post-SPMD cost analysis): forced
    host devices share one physical CPU, so wall clock cannot measure
    scaling, but billed-FLOPs-per-chip CAN — if sharding replicated the
    compute instead of splitting it, per-chip FLOPs would not drop and the
    ratio would collapse toward 1/width.

Needs >= 2 devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
        python benchmarks/sharded_serving.py --smoke --out BENCH_sharding.json
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import configs, fleet as fl
from repro.core import recompile, scheduler
from repro.core.invocation import InvocationService
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import SamplingConfig
from repro.serving.service import serving_container

ARCH = "deepseek-v3-671b-smoke"
GEOM = dict(slots=2, max_len=64, prompt_buckets=(16, 64))
MESH = (1, 2)
SERVE_KINDS = ("serve_prefill", "serve_decode", "serve_spec_verify")


def _requests(cfg, n: int, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(request_id=i,
                    prompt=rng.integers(0, cfg.vocab_size, (7,),
                                        dtype=np.int32),
                    max_new_tokens=max_new, sampling=SamplingConfig())
            for i in range(n)]


# ---------------------------------------------------------------------------
# capacity: the KV pool that does not fit one chip fits at TP=2
# ---------------------------------------------------------------------------
def capacity(cfg, params, *, requests: int) -> dict:
    fleet_cfg = fl.FleetConfig(min_replicas=1, max_replicas=2,
                               slots=GEOM["slots"], max_len=GEOM["max_len"],
                               prompt_buckets=(8, 16), tick_s=0.1,
                               warm_boot_s=0.2, cold_boot_s=0.5,
                               prefix_cache_mb=0.0,
                               mesh_options=((1, 1), MESH))
    b1 = fl.replica_bytes_per_chip(cfg, fleet_cfg, (1, 1))
    b2 = fl.replica_bytes_per_chip(cfg, fleet_cfg, MESH)
    # model a chip whose HBM sits between the two footprints: one chip
    # cannot hold the replica, two model-parallel shards can
    hbm = (b1 + b2) // 2
    assert b2 <= hbm < b1, f"footprints degenerate: {b1} vs {b2}"
    profile = recompile.host_mesh_profile(MESH, hbm_bytes=hbm)
    fm = fl.FleetManager.build(cfg, params, chips=4, fleet=fleet_cfg,
                               profile=profile)
    wd = fm.width_decision
    assert wd["chips_per_replica"] == 2, f"policy chose {wd}"
    assert "widened past" in wd["reason"], wd["reason"]
    trace = fl.steady_trace(seed=0, duration_s=6.0, prompt_median=6,
                            prompt_lo=4, prompt_hi=8,
                            max_new_lo=4, max_new_hi=6)
    reqs = fl.materialize(trace, vocab_size=cfg.vocab_size, seed=1,
                          max_prompt_len=16)[:requests]
    report = fm.run_trace(reqs)
    assert report.served == report.requests and report.reconciled
    assert all(r["chips"] == 2 for r in report.replicas)
    return {
        "bytes_per_chip_1x1": b1,
        "bytes_per_chip_tp2": b2,
        "hbm_bytes_modeled": hbm,
        "fits_1chip": b1 <= hbm,
        "fits_tp2": b2 <= hbm,
        "width_reason": wd["reason"],
        "fleet_served": report.served,
        "replica_chips": 2,
    }


# ---------------------------------------------------------------------------
# parity: sharded greedy streams byte-identical to single-device
# ---------------------------------------------------------------------------
def _stream(cfg, params, mesh, reqs, **kw) -> dict:
    eng = ServingEngine(cfg, params, mesh=mesh, **GEOM, **kw)
    eng.warmup()
    for r in reqs:
        eng.submit(r)
    return {rid: list(map(int, r.tokens))
            for rid, r in eng.run_to_completion().items()}


def parity(cfg, params, *, requests: int, max_new: int) -> dict:
    mesh = shd.make_mesh(MESH, ("data", "model"))
    paths = {"decode": {},
             "prefill_chunk": dict(page_size=16, kv_pages=9,
                                   prefill_chunk_tokens=16)}
    out = {}
    for name, kw in paths.items():
        ref = _stream(cfg, params, None, _requests(cfg, requests, max_new),
                      **kw)
        got = _stream(cfg, params, mesh, _requests(cfg, requests, max_new),
                      **kw)
        assert got == ref, f"{name}: sharded stream diverged"
        out[name] = True
    return out


# ---------------------------------------------------------------------------
# efficiency: modeled per-chip-second throughput within 20% of one chip
# ---------------------------------------------------------------------------
def throughput_mode(cfg, params, profile, mesh_shape, reqs) -> dict:
    cont = serving_container(cfg, params, mesh_shape=mesh_shape, **GEOM)
    service = InvocationService(scheduler.Cluster(chips=profile.chips))
    with service.acquire_serving("bench", cont, profile) as ex:
        ex.warmup()
        for r in reqs:
            ex.submit(r)
        ex.run()
        tokens = service.meter.served_tokens("bench")
        flop_s = sum(b.flop_s for b in service.meter.bills
                     if b.kind in SERVE_KINDS)
    chip_s = flop_s / profile.peak_flops  # roofline-modeled chip-seconds
    return {
        "mesh": "x".join(map(str, mesh_shape or (1,))),
        "chips": profile.chips,
        "tokens": tokens,
        "billed_flops": flop_s,
        "modeled_chip_s": chip_s,
        "tok_per_chip_s": tokens / max(chip_s, 1e-12),
    }


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: fewer requests, same assertions")
    ap.add_argument("--out", default="BENCH_sharding.json")
    args = ap.parse_args()
    if jax.device_count() < int(np.prod(MESH)):
        raise SystemExit(
            f"needs {int(np.prod(MESH))} devices; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")
    n = 2 if args.smoke else args.requests
    max_new = 4 if args.smoke else args.max_new

    cfg = configs.get_config(ARCH)
    params = transformer.init_model(jax.random.key(0), cfg)

    cap = capacity(cfg, params, requests=max(n, 2))
    gib = 1 / (1 << 30)
    print(f"capacity: 1x1 needs {cap['bytes_per_chip_1x1'] * gib:.4f} "
          f"GiB/chip > modeled HBM {cap['hbm_bytes_modeled'] * gib:.4f} GiB; "
          f"TP=2 needs {cap['bytes_per_chip_tp2'] * gib:.4f} GiB/chip — "
          f"fits, fleet served {cap['fleet_served']} requests")
    print(f"  width policy: {cap['width_reason']}")

    par = parity(cfg, params, requests=n, max_new=max_new)
    print(f"parity: greedy streams byte-identical on {list(par)} "
          f"(mesh {'x'.join(map(str, MESH))} vs single device)")

    reqs = _requests(cfg, n, max_new)
    base = throughput_mode(cfg, params, recompile.detect_profile(), None, reqs)
    shard = throughput_mode(cfg, params, recompile.detect_profile(MESH),
                            MESH, reqs)
    ratio = shard["tok_per_chip_s"] / base["tok_per_chip_s"]
    print(f"throughput (roofline-modeled from billed FLOPs): "
          f"1-chip {base['tok_per_chip_s']:.0f} tok/chip-s, TP=2 "
          f"{shard['tok_per_chip_s']:.0f} tok/chip-s — ratio {ratio:.2f}")
    assert shard["tokens"] == base["tokens"]
    assert ratio >= 0.8, (
        f"TP=2 per-chip throughput ratio {ratio:.2f} < 0.8: sharding is "
        f"duplicating compute instead of splitting it")

    payload = {
        "benchmark": "sharded_serving",
        "arch": ARCH,
        "mesh": list(MESH),
        "smoke": args.smoke,
        "capacity": cap,
        "token_parity": all(par.values()),
        "parity_paths": sorted(par),
        "throughput": {
            "modes": [base, shard],
            "per_chip_throughput_ratio": round(ratio, 4),
        },
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("sharded_serving OK")


if __name__ == "__main__":
    main()
