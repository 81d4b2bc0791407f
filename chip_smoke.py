#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-0.5b at its published width on one TPU,
through the leased path a user drives.

One run leases a chip from ``InvocationService.acquire_serving`` with the
profile detected from the device, deploys and warms the serving container
(Pallas tiers probed, bound and compiled), serves greedy requests through
the contiguous engine and through the paged engine, and checks the answers:

* every request is served, and the ledger meters exactly the tokens served;
* ``attention``, ``decode_attention``, ``paged_decode_attention`` and
  ``rmsnorm`` are bound to the ``pallas-tpu`` tier;
* the paged engine's greedy streams equal the contiguous engine's;
* the first decode step's logits, through the bound tiers and through the
  paged layout, agree with the same model with every accelerated API pinned
  to its portable reference, within ``LOGIT_RTOL``.

Usage (from the root of a checkout; weights are random, from ``--seed``):

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded phase only, on four chips

``--four-chips`` serves the same requests from one (1, 4) tensor-parallel
replica over four devices and from a (1, 1) replica on device 0, checks that
every device holds a shard of the params and of the KV pool, that the lease
bills four chips, and that first-step logits agree.

Without a TPU the script exits non-zero before serving: it never falls back
to the CPU. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Times it prints are
smoke numbers of a single run, not a benchmark.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import pathlib
import sys
import time

# First-step logits of the bound tiers against the portable reference: the
# largest absolute difference over the largest reference magnitude. bf16
# rounds at 2**-8 (3.9e-3 relative); 24 layers of it add up to a few units.
LOGIT_RTOL = 2e-2
PALLAS_APIS = ("attention", "decode_attention", "paged_decode_attention",
               "rmsnorm")


class SmokeFailure(RuntimeError):
    """A phase of the smoke run failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """What one smoke run serves. The default is the chip run: qwen2-0.5b at
    published width, 8 greedy requests of 128-512 prompt tokens and 32 new
    tokens, 8 slots, max_len 1024, KV pages of 16 tokens."""

    arch: str = "qwen2-0.5b"
    requests: int = 8
    prompt_lo: int = 128
    prompt_hi: int = 512
    max_new: int = 32
    slots: int = 8
    max_len: int = 1024
    prompt_buckets: tuple[int, ...] = (128, 256, 512)
    page_size: int = 16


def _model(geo: Geometry, seed: int):
    import jax

    from repro import configs
    from repro.models import transformer

    cfg = configs.get_config(geo.arch)
    return cfg, transformer.init_model(jax.random.key(seed), cfg)


def _requests(cfg, geo: Geometry, seed: int) -> list:
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(request_id=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(geo.prompt_lo,
                                                         geo.prompt_hi + 1)),
                                        dtype=np.int32),
                    max_new_tokens=geo.max_new)
            for i in range(geo.requests)]


def _lease_and_serve(service, tenant, cont, profile, reqs, *, expect_tier,
                     log, failed):
    """Lease, warm and drain one serving container. Returns (results,
    engine, chips the lease held); the lease is released on return. A tier
    other than ``expect_tier`` is appended to ``failed`` and serving goes
    on, so one run reports every check."""
    with service.acquire_serving(tenant, cont, profile) as ex:
        t0 = time.perf_counter()
        man = ex.warmup()
        warm_s = time.perf_counter() - t0
        apis = man["apis"]
        log(f"[{tenant}] lease {ex.lease.lease_id} on {profile.name} "
            f"({ex.lease.chips} chip(s)): warmup {warm_s:.3f} s, "
            f"{man['boot']['warmup_compiles']} compiles (smoke numbers)")
        log(f"[{tenant}] manifest: " + " ".join(
            f"{a}={c['provider']}" for a, c in sorted(apis.items())))
        if expect_tier is not None:
            wrong = [a for a in PALLAS_APIS
                     if apis[a]["provider"] != expect_tier]
            for a in wrong:
                for provider, err in apis[a]["rejected"]:
                    log(f"[{tenant}] probe rejected {a}/{provider}: {err}")
            if wrong:
                failed.append(f"{tenant}: {wrong} not bound to {expect_tier}")
        for r in reqs:
            ex.submit(r)
        t0 = time.perf_counter()
        results = ex.run()
        wall = time.perf_counter() - t0
        tokens = sum(len(r.tokens) for r in results.values())
        ledger = service.meter.served_tokens(tenant)
        log(f"[{tenant}] served {len(results)}/{len(reqs)} requests, "
            f"{tokens} tokens, ledger {ledger} tokens, "
            f"{tokens / wall:.3f} tok/s (smoke number)")
        _check(len(results) == len(reqs) and ex.unserved == 0,
               f"{tenant}: served {len(results)}/{len(reqs)}")
        _check(all(len(r.tokens) == q.max_new_tokens
                   for r, q in zip((results[q.request_id] for q in reqs),
                                   reqs)),
               f"{tenant}: a request stopped short of max_new_tokens")
        _check(ledger == tokens, f"{tenant}: ledger {ledger} != {tokens}")
        return results, ex.engine, ex.lease.chips


def first_step_logits(engine, reqs, results, *, binding=None,
                      page_size=None):
    """Logits of the first decode step of ``reqs``, computed from
    ``engine``'s params, mesh and rules under ``binding`` (the engine's own
    by default): prefill each prompt, then decode the first token the
    engine generated for it. ``page_size`` runs both through the paged
    KV layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hooks
    from repro.distributed import sharding as shd
    from repro.models import transformer

    cfg, max_len = engine.cfg, engine.max_len
    b = len(reqs)
    lens = np.array([len(r.prompt) for r in reqs], np.int32)
    toks = np.zeros((b, int(lens.max())), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :lens[i]] = r.prompt
    nxt = np.array([results[r.request_id].tokens[0] for r in reqs], np.int32)
    dt = jnp.dtype(cfg.activ_dtype)

    def step(params, toks, lens, nxt):
        bt = None
        if page_size is None:
            states = transformer.init_states(cfg, b, max_len, dt)
        else:
            nb = max_len // page_size
            states = transformer.init_paged_states(
                cfg, 1 + b * nb, page_size, dt)
            bt = 1 + jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
        _, states, _ = transformer.prefill_chunk(
            params, cfg, toks, states, jnp.zeros_like(lens), lens,
            block_tables=bt, page_size=page_size)
        logits, _ = transformer.decode_step(
            params, cfg, nxt, states, lens + 1, block_tables=bt,
            page_size=page_size)
        return logits

    with contextlib.ExitStack() as stack:
        if engine.mesh is not None:
            stack.enter_context(engine.mesh)
            stack.enter_context(shd.use_rules(engine.rules, engine.mesh))
        stack.enter_context(hooks.use(binding or engine.binding))
        out = jax.jit(step)(engine.params, toks, lens, nxt)
    return np.asarray(out, np.float32)


def _compare_logits(label, got, want, log) -> list[str]:
    """Log how far ``got`` is from ``want``; return the failed checks."""
    import numpy as np

    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    log(f"[check] first-step logits {label}: max |diff| / max |ref| = "
        f"{err:.6g} (limit {LOGIT_RTOL}), argmax agreement {agree:.3f}")
    if not (np.isfinite(got).all() and err <= LOGIT_RTOL):
        return [f"{label}: logit error {err} > {LOGIT_RTOL}"]
    return []


def run_phases(geo: Geometry, profile, *, expect_tier: str = "pallas-tpu",
               seed: int = 0, log=print) -> dict:
    """The one-chip smoke run: lease, boot, serve and check, on ``profile``
    with the four Pallas APIs expected on ``expect_tier``. Raises
    ``SmokeFailure`` listing every failed check; a phase that cannot go on
    (a request not served, a ledger that does not add up) raises at once."""
    from repro.core import hooks, scheduler
    from repro.core.invocation import InvocationService
    from repro.serving.service import serving_container

    cfg, params = _model(geo, seed)
    reqs = _requests(cfg, geo, seed)
    service = InvocationService(scheduler.Cluster(chips=profile.chips))
    common = dict(slots=geo.slots, max_len=geo.max_len,
                  prompt_buckets=geo.prompt_buckets)
    failed: list[str] = []
    contiguous, eng, _ = _lease_and_serve(
        service, "smoke-contiguous", serving_container(cfg, params, **common),
        profile, reqs, expect_tier=expect_tier, log=log, failed=failed)
    paged, paged_eng, _ = _lease_and_serve(
        service, "smoke-paged",
        serving_container(cfg, params, page_size=geo.page_size, **common),
        profile, reqs, expect_tier=expect_tier, log=log, failed=failed)

    same = sum(paged[r.request_id].tokens == contiguous[r.request_id].tokens
               for r in reqs)
    log(f"[check] paged vs contiguous greedy streams: {same}/{len(reqs)} "
        f"identical")
    if same != len(reqs):
        failed.append("paged and contiguous streams differ")

    portable = hooks.bind(profile, overrides={
        api: "portable" for api in hooks.list_apis()})
    want = first_step_logits(eng, reqs, contiguous, binding=portable)
    failed += _compare_logits(f"{expect_tier} vs portable (contiguous KV)",
                              first_step_logits(eng, reqs, contiguous),
                              want, log)
    failed += _compare_logits(f"{expect_tier} vs portable (paged KV)",
                              first_step_logits(paged_eng, reqs, contiguous,
                                                page_size=geo.page_size),
                              want, log)
    _check(not failed, "; ".join(failed))
    return {"contiguous": contiguous, "paged": paged}


def _devices_holding(tree) -> set:
    import jax

    return {s.device for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def run_sharded_phase(geo: Geometry, mesh_shape: tuple[int, ...], *,
                      seed: int = 0, log=print) -> dict:
    """One (1, M) tensor-parallel replica over M devices against a (1, 1)
    replica on device 0, both leased through the same control plane.
    Raises ``SmokeFailure`` on the first failed check."""
    import jax

    from repro.core import recompile, scheduler
    from repro.core.invocation import InvocationService
    from repro.serving.service import serving_container

    chips = math.prod(mesh_shape)
    cfg, params = _model(geo, seed)
    reqs = _requests(cfg, geo, seed)
    service = InvocationService(scheduler.Cluster(chips=chips))
    common = dict(slots=geo.slots, max_len=geo.max_len,
                  prompt_buckets=geo.prompt_buckets)
    one, one_eng, _ = _lease_and_serve(
        service, "smoke-1x1", serving_container(cfg, params, **common),
        recompile.detect_profile(), reqs, expect_tier=None, log=log,
        failed=[])
    _check(_devices_holding(one_eng.params) == {jax.devices()[0]},
           "the (1, 1) replica's params are not on device 0")
    tag = "x".join(map(str, mesh_shape))
    wide, wide_eng, held = _lease_and_serve(
        service, f"smoke-{tag}",
        serving_container(cfg, params, mesh_shape=mesh_shape, **common),
        recompile.detect_profile(mesh_shape), reqs, expect_tier=None,
        log=log, failed=[])

    mesh_devices = set(wide_eng.mesh.devices.flat)
    for name, tree in (("params", wide_eng.params),
                       ("KV pool", wide_eng.states)):
        leaves = jax.tree.leaves(tree)
        split = sum(leaf.addressable_shards[0].data.shape != leaf.shape
                    for leaf in leaves)
        holders = _devices_holding(tree)
        log(f"[check] {name}: a shard on each of {len(holders)} devices, "
            f"{split}/{len(leaves)} arrays split across them")
        _check(holders == mesh_devices and len(holders) == chips,
               f"{name} not held on every device of the mesh")
    billed = {b.chips for b in service.meter.bills
              if b.tenant == f"smoke-{tag}"}
    log(f"[check] the {tag} lease held {held} chips, bills carry "
        f"chips={sorted(billed)}")
    _check(held == chips and billed == {chips},
           f"lease billed {billed}, not {chips} chips")

    same = sum(wide[r.request_id].tokens == one[r.request_id].tokens
               for r in reqs)
    log(f"[check] {tag} vs 1x1 greedy streams: {same}/{len(reqs)} identical "
        f"(collective reduction order may flip near-tied argmaxes)")
    failed = _compare_logits(f"{tag} replica vs 1x1 replica",
                             first_step_logits(wide_eng, reqs, one),
                             first_step_logits(one_eng, reqs, one), log)
    _check(not failed, "; ".join(failed))
    return {"one": one, "wide": wide}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase: a (1, 4) replica "
                         "against a (1, 1) replica, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform!r} "
              f"devices); refusing to run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    try:
        from repro.core import recompile
    except ImportError as err:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({err})", file=sys.stderr)
        return 2
    recompile.enable_compile_cache()
    print(f"chip_smoke: {len(devices)} x {dev.device_kind} ({dev.platform}), "
          f"jax {jax.__version__}", flush=True)
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    try:
        if args.four_chips:
            run_sharded_phase(Geometry(), (1, 4), seed=args.seed, log=log)
        else:
            run_phases(Geometry(), recompile.detect_profile(),
                       seed=args.seed, log=log)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
