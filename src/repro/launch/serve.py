"""Serving driver — leased continuous-batching engine over a deployed model.

Serving is a first-class XaaS workload here: the driver acquires a
SERVICE-class lease from the ``InvocationService`` control plane, the lease's
deployment boots the ``ServingEngine`` (fused data plane: one compiled
program per decode step, one host sync per step), traffic flows through the
lease, and every served token lands in the tenant's accounting ledger.

``--fleet`` switches to the elastic multi-replica control plane
(``repro.fleet``): N leased replicas behind the affinity router, SLO-driven
autoscaling with BATCH preemption, and per-tenant metering aggregated across
replicas — the same objects the fleet benchmark simulates, driven live.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --requests 16 --max-new 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --fleet [--trace bursty|diurnal|steady] [--max-replicas 4]
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --fleet --disagg [--prefill-pool 1 2] [--decode-pool 1 2]
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --arch deepseek-v3-671b --smoke \
      --mesh 1x2   # tensor/expert-parallel sharded replica
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core import recompile, scheduler
from repro.core.invocation import InvocationService
from repro.models import transformer
from repro.serving.engine import Request
from repro.serving.sampling import SamplingConfig
from repro.serving.service import serving_container

__all__ = ["run", "run_fleet", "main"]


def run(arch_id: str, *, requests: int = 8, max_new: int = 16,
        slots: int = 4, max_len: int = 256, prompt_len: int = 24,
        smoke: bool = True, temperature: float = 0.0, seed: int = 0,
        tenant: str = "serve-demo", fused: bool = True,
        sync_every: int = 1, prefix_cache_mb: float = 0.0,
        shared_prefix_len: int = 0, spec_k: int = 0,
        spec_proposer: str = "ngram", draft_arch: str | None = None,
        page_size: int | None = None, kv_pages: int | None = None,
        kv_watermark: float = 0.05,
        prefill_chunk_tokens: int | None = None,
        artifact_store_dir: str | None = None,
        mesh: tuple[int, ...] | None = None) -> dict:
    arch = arch_id + ("-smoke" if smoke and not arch_id.endswith("-smoke") else "")
    cfg = configs.get_config(arch)
    rng = np.random.default_rng(seed)
    params = transformer.init_model(jax.random.key(seed), cfg)

    store = None
    if artifact_store_dir:
        from repro.checkpoint.store import ArtifactStore
        store = ArtifactStore(artifact_store_dir)

    spec = None
    if spec_k > 0:
        from repro.serving.speculative import SpecConfig
        spec = SpecConfig(k=spec_k, proposer=spec_proposer,
                          draft_arch=draft_arch)

    # control plane: schedule chips, deploy the container, boot the engine
    need = int(np.prod(mesh)) if mesh is not None else 1
    if jax.device_count() < need:
        raise SystemExit(
            f"--mesh {'x'.join(map(str, mesh))} needs {need} devices but "
            f"only {jax.device_count()} visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    profile = recompile.detect_profile(mesh)
    cont = serving_container(cfg, params, slots=slots, max_len=max_len,
                             prompt_buckets=(32, 64, 128), fused=fused,
                             sync_every=sync_every,
                             prefix_cache_bytes=int(prefix_cache_mb * (1 << 20))
                             or None, spec=spec, page_size=page_size,
                             kv_pages=kv_pages, kv_watermark=kv_watermark,
                             prefill_chunk_tokens=prefill_chunk_tokens,
                             artifact_store=store,
                             mesh_shape=(tuple(mesh) if mesh is not None
                                         and int(np.prod(mesh)) > 1 else None))
    cluster = scheduler.Cluster(chips=profile.chips)
    service = InvocationService(cluster)
    # the executor is a context manager: the SERVICE lease is released on
    # every exit path (shutdown OR error), so the chips always return to the
    # cluster free pool — a leaked lease would pin them forever
    with service.acquire_serving(tenant, cont, profile) as executor:
        t0 = time.perf_counter()
        man = executor.warmup()
        boot = (man or {}).get("boot", {})
        print(f"warmup ({boot.get('path', 'cold')}-boot, "
              f"{boot.get('warmup_compiles', '?')} compiles, key "
              f"{boot.get('bundle_key', '-')}): "
              f"{time.perf_counter() - t0:.1f}s")
        if boot.get("fallthrough"):
            for why in boot["fallthrough"]:
                print(f"  boot fallthrough: {why}")
        mman = (man or {}).get("mesh")
        if mman and int(np.prod(mman["shape"])) > 1:
            print(f"mesh {'x'.join(str(d) for d in mman['shape'])} "
                  f"({','.join(mman['axes'])}) — sharded replica on "
                  f"{executor.lease.chips} leased chips")

        lead = (cfg.num_codebooks,) if cfg.frontend == "audio" else ()
        sys_prompt = rng.integers(0, cfg.vocab_size,
                                  lead + (shared_prefix_len,), dtype=np.int32)
        for i in range(requests):
            plen = int(rng.integers(prompt_len // 2, prompt_len + 1))
            prompt = rng.integers(0, cfg.vocab_size, lead + (plen,),
                                  dtype=np.int32)
            if shared_prefix_len:
                prompt = np.concatenate([sys_prompt, prompt], axis=-1)
            executor.submit(Request(request_id=i, prompt=prompt,
                                    max_new_tokens=max_new,
                                    sampling=SamplingConfig(temperature=temperature)))

        t0 = time.perf_counter()
        results = executor.run()
        wall = time.perf_counter() - t0
        stats = dict(executor.engine.stats)
        toks = sum(len(r.tokens) for r in results.values())
        ledger_tokens = service.meter.served_tokens(tenant)
        billed = service.meter.total_usd(tenant)

    assert not executor.lease.active
    assert cluster.free_chips == cluster.total_chips, (
        f"lease released but {cluster.total_chips - cluster.free_chips} "
        f"chip(s) missing from the free pool")

    print(f"lease {executor.lease.lease_id} ({tenant}): served "
          f"{len(results)}/{requests} requests, {toks} tokens in "
          f"{wall:.1f}s ({toks / max(wall, 1e-9):.1f} tok/s) | "
          f"prefills {stats['prefills']} ({stats['prefill_calls']} calls) "
          f"decode steps {stats['decode_steps']} "
          f"syncs/step {stats['host_syncs_decode'] / max(stats['decode_steps'], 1):.2f}")
    if prefix_cache_mb:
        hits, misses = stats["prefix_hits"], stats["prefix_misses"]
        print(f"prefix cache: {hits}/{hits + misses} hits "
              f"({stats['prefix_hit_tokens']} prompt tokens restored, "
              f"{stats['prefill_tokens']} padded positions prefilled)")
    if spec is not None:
        sm = executor.engine.spec_summary()
        print(f"speculative[{sm['proposer']} k={sm['k']}]: "
              f"{sm['accepted']}/{sm['drafted']} drafts accepted "
              f"({sm['acceptance_rate']:.0%}), "
              f"{sm['tokens_per_slot_step']:.2f} tokens/slot-step")
    pg = executor.engine.paged_summary()
    if pg is not None:
        print(f"paged kv[page={pg['page_size']}]: peak "
              f"{pg['peak_in_use']}/{pg['pages_total']} pages "
              f"({pg['cow_copies']} CoW copies, "
              f"{pg['cow_shared_pages']} pages shared now) | "
              f"{pg['preemptions']} preemptions, "
              f"{pg['admit_skips']} watermark skips, "
              f"{stats['chunk_prefill_calls']} chunked prefill calls")
    lat = executor.engine.latency_summary()
    print(f"latency: ttft p50 {lat['ttft_p50_s'] * 1e3:.1f}ms "
          f"p95 {lat['ttft_p95_s'] * 1e3:.1f}ms | tpot p50 "
          f"{lat['tpot_p50_s'] * 1e3:.1f}ms p95 {lat['tpot_p95_s'] * 1e3:.1f}ms")
    print(f"ledger[{tenant}]: {ledger_tokens} tokens metered, "
          f"${billed:.6f} billed across "
          f"{len([b for b in service.meter.bills if b.tenant == tenant])} line items")
    return {"results": results, "stats": stats, "wall_s": wall,
            "tokens": toks, "ledger_tokens": ledger_tokens,
            "billed_usd": billed, "service": service}


def run_fleet(arch_id: str, *, trace_kind: str = "bursty", smoke: bool = True,
              seed: int = 0, chips: int = 4, min_replicas: int = 1,
              max_replicas: int = 4, slots: int = 2, max_len: int = 64,
              duration_s: float = 24.0, batch_jobs: int = 2,
              batch_steps: int = 30, prefix_cache_mb: float = 16.0,
              shared_prefix_len: int = 0, multi_turn: bool = False,
              spec_k: int = 0, spec_proposer: str = "ngram",
              draft_arch: str | None = None, page_size: int | None = None,
              kv_pages: int | None = None,
              artifact_store_dir: str | None = None,
              mesh: tuple[int, ...] | None = None,
              mesh_options: tuple[tuple[int, ...], ...] | None = None,
              disagg: bool = False, prefill_min: int = 1,
              prefill_max: int = 2, decode_min: int = 1,
              decode_max: int = 2) -> dict:
    """Drive the elastic fleet live: same control plane the benchmark
    simulates (repro.fleet), printed as an operator would see it."""
    from repro import fleet as fl

    store = None
    if artifact_store_dir:
        from repro.checkpoint.store import ArtifactStore
        store = ArtifactStore(artifact_store_dir)

    arch = arch_id + ("-smoke" if smoke and not arch_id.endswith("-smoke") else "")
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(seed), cfg)
    makers = {"bursty": fl.bursty_trace, "diurnal": fl.diurnal_trace,
              "steady": fl.steady_trace}
    trace = makers[trace_kind](seed=seed, duration_s=duration_s,
                               prompt_median=8, prompt_lo=4, prompt_hi=16,
                               max_new_lo=4, max_new_hi=8)
    reqs = fl.materialize(trace, vocab_size=cfg.vocab_size, seed=seed + 1,
                          num_codebooks=(cfg.num_codebooks
                                         if cfg.frontend == "audio" else 0),
                          shared_prefix_len=shared_prefix_len,
                          multi_turn=multi_turn, max_prompt_len=max_len // 2)
    if disagg and page_size is None:
        page_size = 8  # disaggregation rides the paged-KV handoff plane
    fleet_cfg = fl.FleetConfig(min_replicas=min_replicas,
                               max_replicas=max_replicas, slots=slots,
                               max_len=max_len, prompt_buckets=(8, 16, 32),
                               tick_s=0.1, warm_boot_s=0.5, cold_boot_s=1.5,
                               prefix_cache_mb=prefix_cache_mb,
                               spec_k=spec_k, spec_proposer=spec_proposer,
                               spec_draft_arch=draft_arch,
                               page_size=page_size, kv_pages=kv_pages,
                               artifact_store=store,
                               mesh_shape=(tuple(mesh) if mesh else None),
                               mesh_options=mesh_options)
    if disagg:
        fm = fl.DisaggFleetManager.build(
            cfg, params, chips=chips, fleet=fleet_cfg,
            disagg=fl.DisaggConfig(prefill_min=prefill_min,
                                   prefill_max=prefill_max,
                                   decode_min=decode_min,
                                   decode_max=decode_max),
            batch_jobs=[(1, batch_steps)] * batch_jobs)
    else:
        fm = fl.FleetManager.build(
            cfg, params, chips=chips, fleet=fleet_cfg,
            batch_jobs=[(1, batch_steps)] * batch_jobs)
    t0 = time.perf_counter()
    report = fm.run_trace(reqs)
    wall = time.perf_counter() - t0

    print(f"fleet[{arch} x{trace_kind}]: {report.served}/{report.requests} "
          f"requests, {report.tokens} tokens over {report.duration_s:.1f} "
          f"virtual s ({wall:.1f}s real) | p50 {report.latency_p50_s:.2f}s "
          f"p99 {report.latency_p99_s:.2f}s | {report.serving_chip_s:.1f} "
          f"serving chip-s, utilization {report.utilization:.0%}")
    print(f"elasticity: {report.scale_ups} scale-ups, {report.scale_downs} "
          f"scale-downs, {report.lease_releases} lease releases, "
          f"{report.preemptions} batch preemptions "
          f"({report.batch.get('resumes', 0)} checkpoint-resumes)")
    if report.width_decision:
        print(f"replica width: {report.width_decision['reason']}")
    pc = report.prefix_cache
    if pc.get("enabled"):
        print(f"prefix cache: {pc['hits']}/{pc['hits'] + pc['misses']} hits "
              f"({pc['hit_tokens']} tokens restored) | router: "
              f"{pc['prefix_affinity_routes']} prefix-affinity routes, "
              f"{pc['session_affinity_routes']} session routes")
    sp = report.speculative
    if sp.get("enabled"):
        print(f"speculative: {sp['accepted']}/{sp['drafted']} drafts "
              f"accepted ({sp['acceptance_rate']:.0%}) across "
              f"{sp['steps']} verify steps")
    pk = report.paged_kv
    if pk.get("enabled"):
        print(f"paged kv: peak {pk['peak_in_use']}/{pk['pages_total']} pages "
              f"fleet-wide | {pk['cow_copies']} CoW copies, "
              f"{pk['preemptions']} preemptions, "
              f"{pk['admit_skips']} watermark skips")
    bt = report.boot
    if bt.get("paths"):
        by_path = " ".join(f"{k}x{v}" for k, v in sorted(bt["paths"].items()))
        print(f"boot ladder: {by_path} | real warmup "
              + " ".join(f"{k}={v:.2f}s"
                         for k, v in sorted(bt["wall_s_by_path"].items()))
              + f" | next boot est {bt['expected_next_boot_s']:.2f} virtual s")
    dg = report.disagg
    if dg.get("enabled"):
        ho = dg["handoff"]
        pools = dg["pools"]
        print(f"disagg: {ho['installed']}/{ho['submitted']} KV handoffs "
              f"installed ({ho['bytes'] / 1e6:.2f} MB, "
              f"{ho['sha_rejected']} sha-rejects, "
              f"{dg['fallback_submits']} fallback colocations) | pools "
              + " ".join(f"{p}={v['live']}/{v['peak']}peak"
                         f"(+{v['scale_ups']}up)"
                         for p, v in sorted(pools.items())))
        print(f"virtual ttft: p50 {report.ttft_virtual_p50_s:.2f}s "
              f"p99 {report.ttft_virtual_p99_s:.2f}s (arrival -> first token)")
    print(f"engine latency: ttft p95 {report.ttft_p95_s * 1e3:.1f}ms | "
          f"tpot p95 {report.tpot_p95_s * 1e3:.1f}ms (real wall clock)")
    for t, what in fm.timeline:
        print(f"  [{t:7.2f}s] {what}")
    for tenant in sorted(report.tokens_by_tenant):
        print(f"ledger[{tenant}]: {report.metered_by_tenant[tenant]} tokens "
              f"metered (${fm.service.meter.total_usd(tenant):.6f})")
    assert report.served == report.requests
    assert report.reconciled, "per-tenant ledger does not reconcile"
    return {"report": report, "manager": fm}


def _parse_mesh(text: str) -> tuple[int, ...]:
    try:
        shape = tuple(int(d) for d in text.lower().split("x"))
        assert shape and all(d >= 1 for d in shape)
        return shape
    except (ValueError, AssertionError):
        raise argparse.ArgumentTypeError(
            f"mesh {text!r} is not DxM (e.g. 1x2)") from None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--tenant", default="serve-demo")
    ap.add_argument("--sync-every", type=int, default=1)
    ap.add_argument("--unfused", action="store_true",
                    help="legacy host-loop data plane (before/after reference)")
    ap.add_argument("--fleet", action="store_true",
                    help="elastic multi-replica fleet mode")
    ap.add_argument("--trace", default="bursty",
                    choices=["bursty", "diurnal", "steady"])
    ap.add_argument("--duration", type=float, default=24.0)
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--batch-jobs", type=int, default=2)
    ap.add_argument("--prefix-cache-mb", type=float, default=16.0,
                    help="radix prefix-cache byte budget per engine/replica "
                         "(0 disables KV reuse)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of shared system prompt prepended to every "
                         "request (per tenant in fleet mode)")
    ap.add_argument("--multi-turn", action="store_true",
                    help="fleet sessions extend their previous prompt")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV: page granularity in tokens (unset keeps "
                         "contiguous per-slot KV strips)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="paged KV pool size in pages incl. the null page "
                         "(unset = full provisioning, slots*max_len tokens)")
    ap.add_argument("--kv-watermark", type=float, default=0.05,
                    help="free-page fraction admission keeps in reserve")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max tokens per chunked-prefill step (paged mode)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: drafts per step (0 disables)")
    ap.add_argument("--spec-proposer", default="ngram",
                    choices=["ngram", "draft"])
    ap.add_argument("--draft-arch", default=None,
                    help="draft model config id (with --spec-proposer draft)")
    ap.add_argument("--mesh", type=_parse_mesh, default=None, metavar="DxM",
                    help="per-replica mesh geometry, e.g. 1x2: shards the "
                         "data plane tensor/expert-parallel across that many "
                         "chips (on CPU set XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N first). In --fleet mode "
                         "fixes every replica's width; unset keeps the "
                         "single-device portability floor")
    ap.add_argument("--mesh-options", default=None, metavar="DxM,DxM,...",
                    help="with --fleet: candidate replica widths; the "
                         "manager picks the narrowest whose per-chip "
                         "footprint fits HBM and logs the width-vs-count "
                         "decision in the timeline")
    ap.add_argument("--artifact-store", default=None, metavar="DIR",
                    help="persistent AOT artifact store directory: first run "
                         "cold-boots and persists serialized executables, "
                         "later runs IR-boot from them (docs/ir-containers.md)")
    ap.add_argument("--disagg", action="store_true",
                    help="with --fleet: split into prefill/decode pools with "
                         "KV page handoff (docs/disaggregation.md)")
    ap.add_argument("--prefill-pool", type=int, nargs=2, default=(1, 2),
                    metavar=("MIN", "MAX"),
                    help="disagg prefill pool size bounds")
    ap.add_argument("--decode-pool", type=int, nargs=2, default=(1, 2),
                    metavar=("MIN", "MAX"),
                    help="disagg decode pool size bounds")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    recompile.enable_compile_cache()
    if args.fleet:
        run_fleet(args.arch, trace_kind=args.trace, smoke=args.smoke,
                  seed=args.seed, chips=args.chips,
                  min_replicas=args.min_replicas,
                  max_replicas=args.max_replicas,
                  duration_s=args.duration, batch_jobs=args.batch_jobs,
                  prefix_cache_mb=args.prefix_cache_mb,
                  shared_prefix_len=args.shared_prefix,
                  multi_turn=args.multi_turn, spec_k=args.spec_k,
                  spec_proposer=args.spec_proposer,
                  draft_arch=args.draft_arch, page_size=args.page_size,
                  kv_pages=args.kv_pages,
                  artifact_store_dir=args.artifact_store,
                  mesh=args.mesh,
                  mesh_options=(tuple(_parse_mesh(m) for m in
                                      args.mesh_options.split(","))
                                if args.mesh_options else None),
                  disagg=args.disagg,
                  prefill_min=args.prefill_pool[0],
                  prefill_max=args.prefill_pool[1],
                  decode_min=args.decode_pool[0],
                  decode_max=args.decode_pool[1])
        return
    out = run(args.arch, requests=args.requests, max_new=args.max_new,
              slots=args.slots, max_len=args.max_len,
              prompt_len=args.prompt_len, smoke=args.smoke,
              temperature=args.temperature, tenant=args.tenant,
              fused=not args.unfused, sync_every=args.sync_every,
              prefix_cache_mb=args.prefix_cache_mb,
              shared_prefix_len=args.shared_prefix, spec_k=args.spec_k,
              spec_proposer=args.spec_proposer, draft_arch=args.draft_arch,
              page_size=args.page_size, kv_pages=args.kv_pages,
              kv_watermark=args.kv_watermark,
              prefill_chunk_tokens=args.prefill_chunk,
              artifact_store_dir=args.artifact_store,
              mesh=args.mesh)
    assert len(out["results"]) == args.requests
    assert out["ledger_tokens"] == out["tokens"]


if __name__ == "__main__":
    main()
