"""Boot-latency benchmark: the IR-boot ladder vs cold trace+compile.

A serving replica boots through a three-rung ladder (docs/ir-containers.md):

  * **cold** — trace + XLA-compile every data-plane program, then persist
    the serialized executables into the container's ``ArtifactStore``.
  * **warm** — an in-process engine with the same bundle key reuses the
    already-compiled program cache (the intra-process rung).
  * **IR**   — a FRESH process (simulated with ``clear_program_caches()``)
    deserializes the persisted executables and installs them: zero traces,
    zero compiles, sub-second boot.

The headline is the IR-vs-cold wall-clock ratio, and the contract is the
same as every other acceleration in this repo: byte-identical greedy token
streams across all three rungs — an IR boot is a faster way to reach the
SAME executable, never a behavior change. Both are asserted here
(``ir_speedup >= 3x`` hard; parity always) and re-gated by
``benchmarks/validate_bench.py`` on the committed ``BENCH_boot.json``.

``--smoke`` is the CI variant: boots the same ``serving_container`` twice
through the real control plane (``InvocationService.acquire_serving``) with
a program-cache clear in between, and asserts the second boot lands on the
IR rung with zero warmup compiles.

    PYTHONPATH=src python benchmarks/boot_latency.py [--repeats 2]
    PYTHONPATH=src python benchmarks/boot_latency.py --smoke --out /tmp/b.json
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint.store import ArtifactStore
from repro.core import recompile
from repro.models import transformer
from repro.serving.engine import Request, ServingEngine, clear_program_caches
from repro.serving.sampling import SamplingConfig

ARCH = "qwen2-0.5b-smoke"
GEOM = dict(slots=2, max_len=32, prompt_buckets=(8,))


def _requests(cfg, n: int, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(request_id=i,
                    prompt=rng.integers(0, cfg.vocab_size, (6,),
                                        dtype=np.int32),
                    max_new_tokens=max_new, sampling=SamplingConfig())
            for i in range(n)]


def _boot_and_serve(cfg, params, store, reqs, *, expect: str) -> dict:
    """One rung: construct + warmup an engine (timed), assert the ladder
    landed where expected, then serve the stream for the parity check."""
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, params, artifact_store=store, **GEOM)
    man = engine.warmup()
    boot_s = time.perf_counter() - t0
    boot = man["boot"]
    assert boot["path"] == expect, (
        f"expected {expect}-boot, got {boot['path']} "
        f"(fallthrough: {boot['fallthrough']})")
    if expect in ("warm", "ir"):
        assert boot["warmup_compiles"] == 0, (
            f"{expect}-boot re-traced {boot['warmup_compiles']} program(s)")
    for r in reqs:
        engine.submit(r)
    results = engine.run_to_completion()
    return {
        "mode": expect,
        "boot_s": boot_s,
        "warmup_compiles": boot["warmup_compiles"],
        "programs_installed": boot["programs"]["installed"],
        "bundle_key": boot["bundle_key"],
        "results": {rid: r.tokens for rid, r in results.items()},
    }


def bench(cfg, params, reqs, repeats: int) -> list[dict]:
    """Cold -> warm -> IR, ``repeats`` times (fresh store per trial so the
    cold rung stays cold); keeps the fastest trial per rung. Token streams
    are asserted identical across every rung of every trial."""
    best: dict[str, dict] = {}
    golden = None
    for _ in range(max(repeats, 1)):
        with tempfile.TemporaryDirectory() as d:
            store = ArtifactStore(d)
            clear_program_caches()
            rows = [_boot_and_serve(cfg, params, store, reqs, expect="cold")]
            rows.append(_boot_and_serve(cfg, params, store, reqs,
                                        expect="warm"))
            clear_program_caches()
            rows.append(_boot_and_serve(cfg, params, store, reqs,
                                        expect="ir"))
        for row in rows:
            if golden is None:
                golden = row["results"]
            assert row["results"] == golden, (
                f"{row['mode']}-boot changed a greedy token stream")
            cur = best.get(row["mode"])
            if cur is None or row["boot_s"] < cur["boot_s"]:
                best[row["mode"]] = row
    return [best["cold"], best["warm"], best["ir"]]


def smoke(cfg, params) -> dict:
    """CI boot-path smoke: deploy + boot the same container twice through
    the control plane; the second boot (fresh program caches, same store)
    must land on the IR rung."""
    from repro.core import scheduler
    from repro.core.invocation import InvocationService
    from repro.serving.service import serving_container

    reqs = _requests(cfg, 2, 4)
    profile = recompile.detect_profile()
    boots = []
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(d)
        golden = None
        for i in range(2):
            clear_program_caches()
            cont = serving_container(cfg, params, artifact_store=store,
                                     **GEOM)
            cluster = scheduler.Cluster(chips=profile.chips)
            service = InvocationService(cluster)
            t0 = time.perf_counter()
            with service.acquire_serving("boot-smoke", cont,
                                         profile) as executor:
                man = executor.warmup()
                boot_s = time.perf_counter() - t0
                for r in reqs:
                    executor.submit(r)
                results = {rid: r.tokens
                           for rid, r in executor.run().items()}
            boot = man["boot"]
            if golden is None:
                golden = results
            assert results == golden, "reboot changed a greedy token stream"
            boots.append({"mode": boot["path"], "boot_s": boot_s,
                          "warmup_compiles": boot["warmup_compiles"],
                          "programs_installed": boot["programs"]["installed"],
                          "bundle_key": boot["bundle_key"],
                          "results": results})
    assert boots[0]["mode"] == "cold", (
        f"first boot should be cold, got {boots[0]['mode']}")
    assert boots[1]["mode"] == "ir", (
        f"second boot should be ir, got {boots[1]['mode']}")
    assert boots[1]["warmup_compiles"] == 0, "IR boot re-traced programs"
    return {"boots": boots}


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=2,
                    help="cold/warm/IR trials; fastest per rung is kept")
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: boot the same container twice via the "
                         "control plane, assert the second boot is IR")
    ap.add_argument("--out", default="BENCH_boot.json")
    args = ap.parse_args()

    cfg = configs.get_config(ARCH)
    params = transformer.init_model(jax.random.key(0), cfg)

    if args.smoke:
        sm = smoke(cfg, params)
        modes = sm["boots"]
    else:
        reqs = _requests(cfg, args.requests, args.max_new)
        modes = bench(cfg, params, reqs, args.repeats)

    by = {m["mode"]: m for m in modes}
    cold_s = by["cold"]["boot_s"]
    ir_s = by["ir"]["boot_s"]
    ir_speedup = cold_s / max(ir_s, 1e-9)

    hdr = f"{'mode':<6} {'boot_s':>8} {'compiles':>9} {'installed':>10}"
    print(f"\narch={ARCH} slots={GEOM['slots']} max_len={GEOM['max_len']}")
    print(hdr)
    print("-" * len(hdr))
    for m in modes:
        print(f"{m['mode']:<6} {m['boot_s']:>8.3f} "
              f"{m['warmup_compiles']:>9} {m['programs_installed']:>10}")
    print(f"\nIR-boot speedup vs cold: {ir_speedup:.1f}x "
          f"({cold_s:.2f}s -> {ir_s:.2f}s), byte-identical greedy streams")

    # the acceptance gate: IR-boot must beat cold trace+compile by >= 3x
    assert ir_speedup >= 3.0, (
        f"IR-boot speedup {ir_speedup:.1f}x < 3x gate "
        f"(cold {cold_s:.2f}s, ir {ir_s:.2f}s)")

    payload = {
        "benchmark": "boot_latency",
        "arch": ARCH,
        "slots": GEOM["slots"],
        "max_len": GEOM["max_len"],
        "smoke": args.smoke,
        "ir_speedup": round(ir_speedup, 3),
        "cold_boot_s": round(cold_s, 4),
        "warm_boot_s": round(by["warm"]["boot_s"], 4) if "warm" in by else None,
        "ir_boot_s": round(ir_s, 4),
        "token_parity": True,  # asserted above on every rung
        "modes": [{k: v for k, v in m.items() if k != "results"}
                  for m in modes],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    print("boot_latency OK")


if __name__ == "__main__":
    main()
