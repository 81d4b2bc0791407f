"""Sharding rules and the sharded serving data plane.

Deterministic half of the sharding suite (the guarded_spec hypothesis
properties live in test_sharding_props.py): param/state rule totality,
recipe rule composition, and — on a forced multi-device host platform
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the dedicated CI
step) — sharded-vs-unsharded greedy stream parity across every serving
path plus fleet metering over multi-chip replicas. On a single-device run
those tests skip and the portability-floor tests still execute.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.launch import recipes as rec
from repro.models import transformer

NDEV = jax.device_count()
needs_2dev = pytest.mark.skipif(
    NDEV < 2, reason="needs >=2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs_8dev = pytest.mark.skipif(
    NDEV < 8, reason="needs 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _mesh(shape=(2, 4), axes=("data", "model")):
    # multiple *logical* devices are not needed: guarded_spec only reads
    # mesh.shape, so a 1-device abstract mesh works
    devs = np.array(jax.devices() * int(np.prod(shape)))[: int(np.prod(shape))]
    return jax.sharding.Mesh(devs.reshape(shape), axes)


MESH = _mesh()


def test_guarded_spec_tuple_degrade():
    """batch=256 on a ("pod","data","model") product that doesn't divide
    degrades to the longest dividing prefix."""
    mesh = _mesh((2, 4, 2), ("pod", "data", "model"))
    rules = dict(shd.RULES_2D, batch=("pod", "data", "model"))
    with shd.use_rules(rules, mesh):
        spec = shd.guarded_spec((8, 16), ("batch", None))
    assert tuple(spec)[0] == ("pod", "data")  # 8 % (2*4*2) != 0 -> drop model


@pytest.mark.parametrize("arch", list(configs.ARCH_IDS))
def test_param_rules_total(arch):
    """Every parameter of every arch matches a PARAM_RULES entry and gets a
    valid spec on the production-shaped mesh."""
    cfg = configs.get_config(arch + "-smoke")
    params = jax.eval_shape(
        lambda: transformer.init_model(jax.random.key(0), cfg))
    axes = shd.logical_param_axes(params)  # raises if any param unmatched
    with shd.use_rules(dict(shd.RULES_2D), MESH):
        specs = shd.param_pspecs(params)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for p, s in zip(flat_p, flat_s):
        assert len(tuple(s)) <= p.ndim


def test_recipe_rules_no_axis_conflicts():
    """Recipe-composed rules never produce duplicate-axis specs (the
    moonshot ZeRO-1 regression: moments spec with 'data' twice)."""
    from repro.training import train_step as ts

    mesh = _mesh((4, 4), ("data", "model"))
    for arch in ("moonshot-v1-16b-a3b", "deepseek-v3-671b", "command-r-plus-104b"):
        recipe = rec.recipe_for(arch, "train_4k")
        rules = rec.rules_for(recipe, multi_pod=False, serving=False)
        cfg = configs.get_config(arch + "-smoke")
        tcfg = rec.train_config_for(cfg, recipe, mesh=mesh, multi_pod=False)
        state = jax.eval_shape(
            lambda: ts.init_train_state(jax.random.key(0), cfg, tcfg))
        with shd.use_rules(rules, mesh):
            specs = ts.train_state_pspecs(state, mesh, tcfg)
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
            flat = []
            for e in tuple(s):
                if e is None:
                    continue
                flat.extend(e if isinstance(e, tuple) else (e,))
            assert len(flat) == len(set(flat)), f"duplicate axes in {s}"


def test_constraint_noop_without_rules():
    x = jnp.ones((4, 4))
    y = shd.constraint(x, "batch", "embed")
    assert y is x  # the portability floor: plain CPU execution untouched


def test_state_rules_cover_all_archs():
    for arch in configs.ARCH_IDS:
        cfg = configs.get_config(arch + "-smoke")
        states = jax.eval_shape(
            lambda: transformer.init_states(cfg, 2, 16, jnp.float32))
        with shd.use_rules(dict(shd.RULES_2D), MESH):
            shd.state_pspecs(states)  # must not raise


_KV_RULES = dict(shd.RULES_2D, kv_seq="pod")  # every KV axis its own mesh axis
_KV_MESH = _mesh((2, 2, 2), ("pod", "data", "model"))


@pytest.mark.parametrize("layout,stacked,want", [
    # paged pool (pages, page, Hkv*D): pages -> state_batch, page slots ->
    # kv_seq, the lane-dense heads -> kv_heads
    ("paged", True, P(None, "data", "pod", "model")),
    ("paged", False, P("data", "pod", "model")),
    # slot cache (B, max_len, Hkv, D) keeps its own rule
    ("slot", True, P(None, "data", "pod", "model", None)),
    ("slot", False, P("data", "pod", "model", None)),
])
def test_state_pspecs_kv_pool_and_slot_cache(layout, stacked, want):
    cfg = configs.get_config("qwen2-0.5b-smoke")
    if layout == "paged":
        states = jax.eval_shape(
            lambda: transformer.init_paged_states(cfg, 8, 16, jnp.float32))
    else:
        states = jax.eval_shape(
            lambda: transformer.init_states(cfg, 2, 16, jnp.float32))
    if not stacked:  # one layer's state, as an unscanned prefix layer holds
        one = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            states["scan"][0])
        states = {"prefix": (one,), "scan": ()}
    with shd.use_rules(_KV_RULES, _KV_MESH):
        specs = shd.state_pspecs(states)
    layer = specs["scan"][0] if stacked else specs["prefix"][0]
    assert layer["k"] == want and layer["v"] == want


# ---------------------------------------------------------------------------
# Sharded serving data plane: stream parity on a real multi-device mesh.
# ---------------------------------------------------------------------------

def _stream(cfg, params, mesh, *, max_new=10, **engine_kw):
    """Greedy token stream for one request through a fresh engine."""
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.sampling import SamplingConfig

    eng = ServingEngine(cfg, params, slots=2, max_len=64,
                        prompt_buckets=(16, 64), mesh=mesh, **engine_kw)
    eng.warmup()
    lead = (cfg.num_codebooks,) if cfg.frontend == "audio" else ()
    prompt = np.arange(int(np.prod(lead + (7,))),
                       dtype=np.int32).reshape(lead + (7,)) % cfg.vocab_size
    eng.submit(Request(request_id=1, prompt=prompt, max_new_tokens=max_new,
                       sampling=SamplingConfig(temperature=0.0)))
    return [int(t) for t in eng.run_to_completion()[1].tokens]


def _engine_kw(path):
    if path == "prefill_chunk":
        return dict(page_size=16, kv_pages=9, prefill_chunk_tokens=16)
    if path == "spec_verify":
        from repro.serving.speculative import SpecConfig
        return dict(spec=SpecConfig(k=2, proposer="ngram"))
    return {}


# one attention arch (GQA) and one MLA+MoE arch: the MoE one routes its FFN
# through kernels/moe_gmm with experts sharded on the "model" axis
PARITY_ARCHS = ("qwen2-0.5b", "deepseek-v3-671b")


@needs_2dev
@pytest.mark.parametrize("path", ["decode", "prefill_chunk", "spec_verify"])
@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_sharded_stream_parity(arch, path):
    """Greedy streams are identical with and without a (1,2) tensor/expert
    parallel mesh, for the fused-decode, paged+chunked-prefill, and
    speculative-verify data planes."""
    cfg = configs.get_config(arch + "-smoke")
    params = transformer.init_model(jax.random.key(0), cfg)
    kw = _engine_kw(path)
    ref = _stream(cfg, params, None, **kw)
    mesh = shd.make_mesh((1, 2), ("data", "model"))
    got = _stream(cfg, params, mesh, **kw)
    assert got == ref, f"{arch}/{path}: sharded stream diverged"


@needs_8dev
def test_sharded_stream_wide_mesh_completes():
    """A (1,4) model-parallel mesh serves a full greedy stream. Exact parity
    with the unsharded stream is only guaranteed at TP=2: wider meshes
    change the float reduction order of collectives, which can flip argmax
    on the near-uniform logits of a random-init smoke model."""
    cfg = configs.get_config("deepseek-v3-671b-smoke")
    params = transformer.init_model(jax.random.key(0), cfg)
    got = _stream(cfg, params, shd.make_mesh((1, 4), ("data", "model")),
                  max_new=6)
    assert len(got) == 6
    assert all(0 <= t < cfg.vocab_size for t in got)


@needs_2dev
def test_data_axis_mesh_rejected():
    """Data parallelism inside one engine is rejected with a clear error —
    replicas scale out, they don't shard the batch."""
    from repro.serving.engine import ServingEngine

    cfg = configs.get_config("qwen2-0.5b-smoke")
    params = transformer.init_model(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="data axis"):
        ServingEngine(cfg, params, slots=2, max_len=32,
                      mesh=shd.make_mesh((2, 1), ("data", "model")))


@needs_2dev
def test_expert_weights_and_kv_pool_sharded():
    """The MoE expert stacks and the paged KV pool are *actually* split
    across the model axis — per-device shards are smaller than the global
    array and span every mesh device."""
    from repro.serving.engine import ServingEngine

    cfg = configs.get_config("deepseek-v3-671b-smoke")
    params = transformer.init_model(jax.random.key(0), cfg)
    mesh = shd.make_mesh((1, 2), ("data", "model"))
    eng = ServingEngine(cfg, params, slots=2, max_len=64,
                        prompt_buckets=(16, 64), mesh=mesh,
                        page_size=16, kv_pages=9)
    hits = []

    def check(path, leaf):
        name = jax.tree_util.keystr(path)
        if "experts" in name and "w_up" in name:
            shard = leaf.sharding.shard_shape(leaf.shape)
            assert shard != leaf.shape, f"{name} not sharded: {leaf.shape}"
            assert len(leaf.devices()) == 2
            hits.append(name)
        return leaf

    jax.tree_util.tree_map_with_path(check, eng.params)
    assert hits, "no expert w_up leaves found"
    # paged KV pool: every state leaf lives on the mesh, model-dim leaves
    # (kv heads / latent) shard when divisible
    for leaf in jax.tree.leaves(eng.states):
        assert len(leaf.devices()) in (1, 2) and leaf.committed


@needs_2dev
def test_fleet_two_chip_replica_meters_all_chips():
    """A fleet of (1,2)-mesh replicas leases 2 chips per replica and every
    serving bill meters device-seconds across BOTH chips."""
    from repro import fleet as fl

    cfg = configs.get_config("qwen2-0.5b-smoke")
    params = transformer.init_model(jax.random.key(0), cfg)
    fleet_cfg = fl.FleetConfig(min_replicas=1, max_replicas=2, slots=2,
                               max_len=32, prompt_buckets=(8, 16),
                               tick_s=0.1, warm_boot_s=0.2, cold_boot_s=0.5,
                               prefix_cache_mb=0.0, mesh_shape=(1, 2))
    fm = fl.FleetManager.build(cfg, params, chips=4, fleet=fleet_cfg)
    trace = fl.steady_trace(seed=0, duration_s=6.0, prompt_median=6,
                            prompt_lo=4, prompt_hi=8,
                            max_new_lo=4, max_new_hi=6)
    reqs = fl.materialize(trace, vocab_size=cfg.vocab_size, seed=1,
                          max_prompt_len=16)
    report = fm.run_trace(reqs)
    assert report.served == report.requests
    assert report.reconciled
    for r in report.replicas:
        assert r["chips"] == 2
        assert r["mesh"] == {"shape": [1, 2], "axes": ["data", "model"]}
    decode = [b for b in fm.service.meter.bills if b.kind == "serve_decode"]
    assert decode, "no decode bills recorded"
    for b in decode:
        assert b.chips == 2
        assert b.device_s == pytest.approx(b.wall_s * 2)
