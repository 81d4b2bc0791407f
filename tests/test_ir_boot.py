"""IR-boot container tests: the persistent-AOT boot ladder.

Covers the three-rung ladder (cold trace+compile -> warm in-process cache
-> IR deserialize-and-install) at byte-identical greedy streams across
plain / speculative / paged engines, stale-artifact invalidation (jax
version drift, kernel-tier drift), corrupt-artifact fallthrough, the
warmup() manifest contract (full manifest + boot record even on a pure
cache hit, zero re-traces on warm/IR rungs), and entrypoint-level IR
restore through the deployment compiler.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint.store import ArtifactStore
from repro.core import aot, container as xc, hooks, recompile, scheduler
from repro.core.invocation import InvocationService
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.serving.engine import (Request, ServingEngine,
                                  clear_program_caches)
from repro.serving.sampling import SamplingConfig
from repro.serving.service import serving_container
from repro.serving.speculative import SpecConfig

GEOM = dict(slots=2, max_len=32, prompt_buckets=(8,))

pytestmark = pytest.mark.skipif(
    not aot.AOT_AVAILABLE, reason="jax AOT serialization unavailable")


@functools.lru_cache(maxsize=2)
def _model(arch="qwen2-0.5b-smoke"):
    cfg = configs.get_config(arch)
    params = transformer.init_model(jax.random.key(0), cfg)
    return cfg, params


def _reqs(cfg, n=3, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(request_id=i,
                    prompt=rng.integers(0, cfg.vocab_size, (6,),
                                        dtype=np.int32),
                    max_new_tokens=max_new, sampling=SamplingConfig())
            for i in range(n)]


def _serve(engine, cfg):
    for r in _reqs(cfg):
        engine.submit(r)
    res = engine.run_to_completion()
    return {rid: r.tokens for rid, r in res.items()}


# ---------------------------------------------------------------------------
# the ladder: cold -> warm -> IR at byte parity (satellite 1)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", ["plain", "spec", "paged"])
def test_boot_ladder_byte_parity(variant, tmp_path):
    cfg, params = _model()
    kw = dict(GEOM)
    if variant == "spec":
        kw["spec"] = SpecConfig(k=2, proposer="ngram")
    elif variant == "paged":
        kw["page_size"] = 8
    store = ArtifactStore(tmp_path / "store")

    clear_program_caches()
    e1 = ServingEngine(cfg, params, artifact_store=store, **kw)
    assert e1.boot_path_preview() == "cold"
    m1 = e1.warmup()
    b1 = m1["boot"]
    assert b1["path"] == "cold"
    assert b1["warmup_compiles"] > 0
    assert b1.get("persisted", 0) > 0          # cold rung persisted the IR
    assert store.contains(e1._bundle_key)
    toks1 = _serve(e1, cfg)

    # warm: same process, program bundle already compiled
    e2 = ServingEngine(cfg, params, artifact_store=store, **kw)
    assert e2.boot_path_preview() == "warm"
    b2 = e2.warmup()["boot"]
    assert b2["path"] == "warm"
    assert b2["warmup_compiles"] == 0
    assert _serve(e2, cfg) == toks1

    # IR: fresh "process" (cleared program caches), store hit
    clear_program_caches()
    e3 = ServingEngine(cfg, params, artifact_store=store, **kw)
    assert e3.boot_path_preview() == "ir"
    b3 = e3.warmup()["boot"]
    assert b3["path"] == "ir"
    assert b3["warmup_compiles"] == 0          # never re-traces installed IR
    assert b3["programs"]["installed"] > 0
    assert b3["bundle_key"] == b1["bundle_key"]
    assert _serve(e3, cfg) == toks1


# ---------------------------------------------------------------------------
# stale-artifact invalidation (satellite 3)
# ---------------------------------------------------------------------------
def test_stale_jax_version_falls_through_to_cold(tmp_path, monkeypatch):
    cfg, params = _model()
    store = ArtifactStore(tmp_path / "store")
    clear_program_caches()
    ServingEngine(cfg, params, artifact_store=store, **GEOM).warmup()
    assert store.keys()

    clear_program_caches()
    real = aot.runtime_fingerprint()
    monkeypatch.setattr(aot, "runtime_fingerprint",
                        lambda: dict(real, jax="999.0.0", jaxlib="999.0.0"))
    e = ServingEngine(cfg, params, artifact_store=store, **GEOM)
    assert e.boot_path_preview() == "cold"     # key includes the version
    m = e.warmup()
    assert m["boot"]["path"] == "cold"
    reasons = " ".join(m["boot"]["fallthrough"])
    assert "stale artifact" in reasons and "jax" in reasons


def test_stale_tier_binding_falls_through_to_cold(tmp_path):
    cfg, params = _model()
    store = ArtifactStore(tmp_path / "store")
    clear_program_caches()
    # persist under the unbound (portable) tier fingerprint ...
    ServingEngine(cfg, params, artifact_store=store, **GEOM).warmup()

    # ... then "re-deploy" with an explicit hook binding: different tier
    # fingerprint -> different bundle key -> loader rejects and re-traces
    clear_program_caches()
    binding = hooks.bind(recompile.PORTABLE_CPU)
    e = ServingEngine(cfg, params, artifact_store=store, binding=binding,
                      **GEOM)
    assert e.boot_path_preview() == "cold"
    m = e.warmup()
    assert m["boot"]["path"] == "cold"
    reasons = " ".join(m["boot"]["fallthrough"])
    assert "stale artifact" in reasons and "tiers" in reasons


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >=2 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
def test_stale_mesh_falls_through_to_cold(tmp_path):
    """Executables persisted by a single-device engine must never IR-boot a
    sharded replica: the mesh geometry is in the bundle key, and the boot
    manifest explains the miss with the mesh diff first."""
    cfg, params = _model()
    store = ArtifactStore(tmp_path / "store")
    clear_program_caches()
    ServingEngine(cfg, params, artifact_store=store, **GEOM).warmup()
    assert store.keys()

    clear_program_caches()
    mesh = shd.make_mesh((1, 2), ("data", "model"))
    e = ServingEngine(cfg, params, artifact_store=store, mesh=mesh, **GEOM)
    assert e.boot_path_preview() == "cold"
    m = e.warmup()
    assert m["boot"]["path"] == "cold"
    reasons = " ".join(m["boot"]["fallthrough"])
    assert "stale artifact" in reasons and "mesh" in reasons


def test_corrupt_artifact_falls_through_without_raising(tmp_path):
    cfg, params = _model()
    store = ArtifactStore(tmp_path / "store")
    clear_program_caches()
    e1 = ServingEngine(cfg, params, artifact_store=store, **GEOM)
    e1.warmup()
    toks1 = _serve(e1, cfg)

    # truncate one committed blob on disk: the sha256 check must reject the
    # whole bundle and the ladder must land on cold, not raise
    blobdir = tmp_path / "store" / e1._bundle_key / "blobs"
    victim = sorted(blobdir.iterdir())[0]
    victim.write_bytes(victim.read_bytes()[: max(1, victim.stat().st_size // 2)])

    clear_program_caches()
    e2 = ServingEngine(cfg, params, artifact_store=store, **GEOM)
    m = e2.warmup()
    assert m["boot"]["path"] == "cold"
    assert store.stats["corrupt"] >= 1
    assert any(r.startswith("ir:") for r in m["boot"]["fallthrough"])
    # the cold rung re-persisted a good bundle, and parity still holds
    assert _serve(e2, cfg) == toks1
    assert store.get(e2._bundle_key) is not None


# ---------------------------------------------------------------------------
# warmup() manifest contract (satellite 4: fix + pin)
# ---------------------------------------------------------------------------
def test_warmup_returns_full_manifest_even_on_pure_cache_hit(tmp_path):
    cfg, params = _model()
    store = ArtifactStore(tmp_path / "store")
    cont = serving_container(cfg, params, artifact_store=store, **GEOM)
    profile = recompile.PORTABLE_CPU
    service = InvocationService(scheduler.Cluster(chips=profile.chips))
    clear_program_caches()
    with service.acquire_serving("boot-pin", cont, profile) as ex:
        m1 = ex.warmup()
        assert m1["boot"]["path"] == "cold"
        # second warmup: EVERY program is a cache hit — still the full
        # manifest (apis + boot), zero re-traces
        m2 = ex.engine.warmup()
        assert m2["apis"] and m2["container"] == cont.name
        assert m2["boot"]["path"] == "warm"
        assert m2["boot"]["warmup_compiles"] == 0
        assert m2["boot"]["bundle_key"] == m1["boot"]["bundle_key"]


def test_ir_boot_installs_without_retracing(tmp_path):
    cfg, params = _model()
    store = ArtifactStore(tmp_path / "store")
    clear_program_caches()
    ServingEngine(cfg, params, artifact_store=store, **GEOM).warmup()

    clear_program_caches()
    e = ServingEngine(cfg, params, artifact_store=store, **GEOM)
    m = e.warmup()
    reg = e._aot_registry()
    assert m["boot"]["path"] == "ir"
    assert m["boot"]["warmup_compiles"] == 0
    counts = reg.counts()
    assert counts["installed"] > 0
    assert counts["exe_hits"] > 0              # warmup dispatched to them
    assert counts["fallbacks"] == 0            # none were discarded


# ---------------------------------------------------------------------------
# entrypoint-level IR restore through the deployment compiler
# ---------------------------------------------------------------------------
def test_entrypoint_ir_boot_across_compilers(tmp_path):
    def fn(a, b):
        return a @ b

    def make_args(mesh):
        sds = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        return (sds, sds), {}, {}

    store = ArtifactStore(tmp_path / "store")
    cont = xc.XContainer(name="ir-demo", entrypoints={"mm": (fn, make_args)},
                         artifact_store=store)
    profile = recompile.PORTABLE_CPU
    x = jnp.ones((16, 16), jnp.float32)

    comp1 = recompile.DeploymentCompiler()
    dep1 = cont.deploy(profile, compiler=comp1)
    assert dep1.artifact("mm").boot == "cold"
    out1 = np.asarray(dep1("mm", x, x))

    # fresh compiler = fresh process: the executable comes back from the
    # container's store, not from a re-trace
    comp2 = recompile.DeploymentCompiler()
    dep2 = cont.deploy(profile, compiler=comp2)
    art2 = dep2.artifact("mm")
    assert art2.boot == "ir"
    assert comp2.stats.get("ir_boots", 0) == 1
    assert dep2.manifest()["entrypoint_boot"]["mm"]["boot"] == "ir"
    np.testing.assert_array_equal(np.asarray(dep2("mm", x, x)), out1)

    # third deploy on the SAME compiler: in-process warm hit, not IR
    dep3 = cont.deploy(profile, compiler=comp2)
    assert dep3.artifact("mm").boot == "warm"
