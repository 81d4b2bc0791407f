"""Compile guards for the chip, without the chip.

The Pallas kernels of the serving path compile here for a *described* TPU
v5e (the TPU compiler is installed; no chip is attached), at the widths the
chip runs: qwen2-0.5b in bf16 for the attention kernels and RMSNorm,
DeepSeek-V3 expert widths for the grouped MoE matmul. A block shape Mosaic
refuses, or a kernel over its VMEM budget, fails here instead of on the chip.

Beside them: the device-kind -> SystemProfile table, and where the entry
points put JAX's persistent compilation cache.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and pytest workers import every
test file.
"""
import dataclasses
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import recompile
from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm
from repro.kernels import paged_attention as pg
from repro.kernels import rmsnorm as rms


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# qwen2-0.5b: 14 query heads, 2 KV heads, head_dim 64, d_model 896
HQ, HKV, D, DM = 14, 2, 64, 896
BF16, I32 = jnp.bfloat16, jnp.int32

CASES = {
    "flash_attention": (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        [((1, 2048, HQ, D), BF16), ((1, 2048, HKV, D), BF16),
         ((1, 2048, HKV, D), BF16)]),
    "decode_attention": (
        lambda q, k, v, lens: dec.decode_attention(q, k, v, lengths=lens),
        [((8, HQ, D), BF16), ((8, 4096, HKV, D), BF16),
         ((8, 4096, HKV, D), BF16), ((8,), I32)]),
    "paged_decode_attention": (
        lambda q, kp, vp, bt, lens: pg.paged_decode_attention(
            q, kp, vp, bt, lengths=lens),
        [((8, HQ, D), BF16), ((513, 16, HKV * D), BF16),
         ((513, 16, HKV * D), BF16), ((8, 64), I32), ((8,), I32)]),
    "rmsnorm": (
        lambda x, w: rms.rmsnorm(x, w),
        [((8, DM), BF16), ((DM,), BF16)]),
    # DeepSeek-V3 routed experts: d_model 7168 -> d_expert 2048
    "moe_swiglu_hidden": (
        lambda x, wg, wu: moe_gmm.moe_swiglu_hidden(x, wg, wu),
        [((8, 128, 7168), BF16), ((8, 7168, 2048), BF16),
         ((8, 7168, 2048), BF16)]),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, specs = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the paged serving programs keep the KV pool in the kernel's layout
# ---------------------------------------------------------------------------
# 2 layers of qwen2-0.5b, 513 pages of 16, 8 rows of 64 blocks, 64-wide chunk
PAGES, PAGE, ROWS, BLOCKS, CHUNK = 513, 16, 8, 64, 64
_COPY = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")


def _paged_program(which, one_chip):
    from repro import configs
    from repro.core import hooks
    from repro.kernels import ops  # noqa: F401  (registers the tiers)
    from repro.models import transformer

    cfg = dataclasses.replace(configs.get_config("qwen2-0.5b"), num_layers=2)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: transformer.init_model(jax.random.key(0), cfg)))
    states = on_chip(jax.eval_shape(
        lambda: transformer.init_paged_states(cfg, PAGES, PAGE, BF16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, I32, sharding=one_chip)  # noqa
    bt = i32(ROWS, BLOCKS)
    if which == "decode":
        def fn(p, t, s, lens, bt, key):
            return transformer.decode_and_sample(
                p, cfg, t, s, lens, key,
                lambda k, lg: jnp.argmax(lg, -1).astype(I32),
                block_tables=bt, page_size=PAGE)
        key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
        args = (params, i32(ROWS), states, i32(ROWS), bt, key)
    else:
        def fn(p, t, s, start, lens, bt):
            return transformer.prefill_chunk(
                p, cfg, t, s, start, lens, block_tables=bt, page_size=PAGE)
        args = (params, i32(ROWS, CHUNK), states, i32(ROWS), i32(ROWS), bt)
    binding = hooks.bind(recompile.TPU_V5E)
    assert binding.choices["paged_decode_attention"].provider == "pallas-tpu"
    with hooks.use(binding):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_paged_programs_never_relay_the_kv_pool(which, one_chip):
    """No ``copy`` in the optimized program has a shape carrying the pool's
    page count: a layer's pool is never re-laid between the layout XLA
    keeps it in and the one the paged kernel reads."""
    hlo = _paged_program(which, one_chip)
    if which == "decode":
        assert "tpu_custom_call" in hlo
    relays = [m.group(0) for m in _COPY.finditer(hlo)
              if str(PAGES) in m.group(1).split(",")]
    assert relays == []


# ---------------------------------------------------------------------------
# device kind -> profile
# ---------------------------------------------------------------------------
def _device(kind, platform):
    return types.SimpleNamespace(device_kind=kind, platform=platform)


def test_v5e_device_maps_to_the_v5e_profile():
    v5e = [_device("TPU v5 lite", "tpu")] * 4
    one = recompile.detect_profile(devices=v5e)
    assert one is recompile.TPU_V5E
    assert one.peak_flops == 197e12 and one.hbm_bw == 819e9
    wide = recompile.detect_profile((1, 4), devices=v5e)
    assert wide.chips == 4 and wide.mesh_shape == (1, 4)
    assert wide.mesh_axes == ("data", "model")
    assert wide.peak_flops == one.peak_flops
    # a Mosaic kernel cannot be partitioned across a multi-device program
    assert not wide.supports("pallas-tpu")


def test_cpu_device_maps_to_the_cpu_profiles():
    cpu = [_device("cpu", "cpu")]
    assert recompile.detect_profile(devices=cpu) is recompile.PORTABLE_CPU
    assert recompile.detect_profile((1, 1), devices=cpu) is (
        recompile.PORTABLE_CPU)
    assert recompile.detect_profile((1, 2), devices=cpu) is (
        recompile.host_mesh_profile((1, 2)))
    assert recompile.detect_profile() is recompile.PORTABLE_CPU


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        recompile.detect_profile(devices=[_device("TPU v9 imaginary", "tpu")])


# ---------------------------------------------------------------------------
# compile-cache placement
# ---------------------------------------------------------------------------
def test_compile_cache_honours_the_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert recompile.compile_cache_dir() is None
    recompile.enable_compile_cache()
    assert calls == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert recompile.compile_cache_dir() == root / ".jax_cache"
    recompile.enable_compile_cache()
    recompile.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir",
                      str(root / ".jax_cache"))] * 2
