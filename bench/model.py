"""What the benchmark hands the program: its model config, the seeded
weights in the program's own tree, and the serving container's geometry.

This is the one file that knows the program's parameter layout. It checks
that layout against ``jax.eval_shape`` of the program's ``init_model``, so a
change of layout fails here instead of serving wrong weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights


def arch_config(name: str, model: dict):
    """The program's ``ArchConfig`` for a Qwen2-family config.json."""
    from repro.configs.base import ArchConfig, LayerSpec

    if model.get("use_sliding_window"):
        raise ValueError(f"{name}: sliding-window layers are not modelled")
    m = weights.dims(model)
    dtype = model.get("torch_dtype", "bfloat16")
    return ArchConfig(
        name=name, family="dense", num_layers=m["layers"], d_model=m["d"],
        num_heads=m["hq"], num_kv_heads=m["hkv"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["v"],
        pattern=(LayerSpec("global_attn", "swiglu"),), qkv_bias=True,
        tie_embeddings=m["tied"], pos="rope",
        rope_theta=float(model["rope_theta"]), norm="rmsnorm",
        param_dtype=dtype, activ_dtype=dtype)


def _program_tree(key, model: dict, dtype):
    m = weights.dims(model)

    def layer(i):
        t = weights.layer_tensors(key, model, i, dtype)
        return {
            "norm1": {"w": t["ln1"]},
            "mixer": {"wq": {"w": t["q_w"], "b": t["q_b"]},
                      "wk": {"w": t["k_w"], "b": t["k_b"]},
                      "wv": {"w": t["v_w"], "b": t["v_b"]},
                      "wo": {"w": t["o_w"]}},
            "norm2": {"w": t["ln2"]},
            "ffn": {"w_gate": {"w": t["gate_w"]}, "w_up": {"w": t["up_w"]},
                    "w_down": {"w": t["down_w"]}},
        }

    params = {"embed": {"w": weights.top_tensor(key, model, "embed", dtype)}}
    if not m["tied"]:
        params["lm_head"] = {"w": weights.top_tensor(key, model, "head",
                                                     dtype)}
    params["final_norm"] = {"w": weights.top_tensor(key, model, "final_ln",
                                                    dtype)}
    params["prefix"] = ()
    params["scan"] = (jax.vmap(layer)(jnp.arange(m["layers"])),)
    return params


def make_params(seed: int, model: dict, cfg):
    """The whole weight tree on the device, in the served dtype, from one
    jitted call. Raises if the program's layout differs."""
    from repro.models import transformer

    dtype = jnp.dtype(cfg.param_dtype)
    want = jax.eval_shape(lambda: transformer.init_model(jax.random.key(0),
                                                         cfg))
    make = jax.jit(lambda k: _program_tree(k, model, dtype))
    got = jax.eval_shape(make, weights.seed_key(0))
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError(
            "the program's parameter layout differs from the one the "
            f"benchmark makes:\nprogram {want}\nbenchmark {got}")
    return make(weights.seed_key(seed))


def container_kwargs(engine: dict) -> dict:
    """``serving_container`` keyword arguments from a config's ``engine``."""
    out = {"slots": engine["slots"], "max_len": engine["max_len"],
           "prompt_buckets": tuple(engine["prompt_buckets"]),
           "page_size": engine["page_size"], "kv_pages": engine["kv_pages"],
           "prefill_chunk_tokens": engine["prefill_chunk_tokens"]}
    if engine.get("prefix_cache_bytes"):
        out["prefix_cache_bytes"] = engine["prefix_cache_bytes"]
    return out
