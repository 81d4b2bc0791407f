"""The Qwen2 family (``model_type`` ``qwen2``; arXiv:2407.10671): dense
pre-norm blocks of grouped-query attention with biased Q/K/V projections
and rotary positions, and a SwiGLU MLP.

Its weight layout: each tensor has its own key,
``fold_in(fold_in(seed_key, tensor id), layer)``, so one layer can be made
alone and equals the same layer made inside the stacked tree. Its counts
follow the rules of ``bench/flops.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference.qwen2 import Q_BLOCK, Reference  # noqa: F401

# tensor id -> (shape from the config, kind); the ids are part of the
# yardstick: renumbering them changes every weight of every cell
LAYER_TENSORS = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w",
                 "gate_w", "up_w", "down_w", "ln1", "ln2")
TOP_TENSORS = ("embed", "head", "final_ln")


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    hq = model["num_attention_heads"]
    hd = model.get("head_dim", d // hq)
    return {"d": d, "f": model["intermediate_size"], "hq": hq,
            "hkv": model["num_key_value_heads"], "hd": hd,
            "v": model["vocab_size"], "layers": model["num_hidden_layers"],
            "tied": bool(model["tie_word_embeddings"])}


def layer_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    m = dims(model)
    d, f, q, kv = m["d"], m["f"], m["hq"] * m["hd"], m["hkv"] * m["hd"]
    return {"q_w": (d, q), "q_b": (q,), "k_w": (d, kv), "k_b": (kv,),
            "v_w": (d, kv), "v_b": (kv,), "o_w": (q, d), "gate_w": (d, f),
            "up_w": (d, f), "down_w": (f, d), "ln1": (d,), "ln2": (d,)}


def layer_tensors(key, model: dict, layer, dtype) -> dict[str, jax.Array]:
    """Every tensor of one layer (``layer`` may be traced)."""
    out = {}
    for i, (name, shape) in enumerate(layer_shapes(model).items()):
        k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
        out[name] = weights.make(k, name, shape, dtype)
    return out


def top_tensor(key, model: dict, name: str, dtype) -> jax.Array:
    m = dims(model)
    shape = {"embed": (m["v"], m["d"]), "head": (m["d"], m["v"]),
             "final_ln": (m["d"],)}[name]
    i = len(LAYER_TENSORS) + TOP_TENSORS.index(name)
    return weights.make(jax.random.fold_in(key, i), name, shape, dtype)


def arch_config(name: str, conf: dict):
    """The program's ``ArchConfig`` for a Qwen2 config.json."""
    from repro.configs.base import ArchConfig, LayerSpec

    model = conf["model"]
    if model.get("use_sliding_window"):
        raise ValueError(f"{name}: sliding-window layers are not modelled")
    m = dims(model)
    dtype = model.get("torch_dtype", "bfloat16")
    return ArchConfig(
        name=name, family="dense", num_layers=m["layers"], d_model=m["d"],
        num_heads=m["hq"], num_kv_heads=m["hkv"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["v"],
        pattern=(LayerSpec("global_attn", "swiglu"),), qkv_bias=True,
        tie_embeddings=m["tied"], pos="rope",
        rope_theta=float(model["rope_theta"]), norm="rmsnorm",
        param_dtype=dtype, activ_dtype=dtype)


def program_tree(key, conf: dict, dtype):
    model = conf["model"]
    m = dims(model)

    def layer(i):
        t = layer_tensors(key, model, i, dtype)
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

    params = {"embed": {"w": top_tensor(key, model, "embed", dtype)}}
    if not m["tied"]:
        params["lm_head"] = {"w": top_tensor(key, model, "head", dtype)}
    params["final_norm"] = {"w": top_tensor(key, model, "final_ln", dtype)}
    params["prefix"] = ()
    params["scan"] = (jax.vmap(layer)(jnp.arange(m["layers"])),)
    return params


def layer_params(model: dict) -> int:
    """Weights one token multiplies in one layer (biases and gains left
    out: they add, not multiply)."""
    m = dims(model)
    q, kv = m["hq"] * m["hd"], m["hkv"] * m["hd"]
    return m["d"] * (2 * q + 2 * kv) + 3 * m["d"] * m["f"]


def head_params(model: dict) -> int:
    m = dims(model)
    return m["d"] * m["v"]


def attn_flops(model: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, all layers and heads."""
    m = dims(model)
    return 4 * m["hq"] * m["hd"] * keys * m["layers"]


def decode_flops(conf: dict, ctx: list[int]) -> int:
    """One decode token per entry of ``ctx``, each attending ``ctx[i]`` keys
    (its own included), with the LM head."""
    model = conf["model"]
    per = 2 * (layer_params(model) * dims(model)["layers"]
               + head_params(model))
    return per * len(ctx) + sum(attn_flops(model, c) for c in ctx)


def prefill_flops(conf: dict, spans: list[tuple[int, int]]) -> int:
    """Prompt positions [a, b) of each span, causal, with the LM head once
    per span (a chunk's logits are taken at its last position)."""
    model = conf["model"]
    per = 2 * layer_params(model) * dims(model)["layers"]
    n = sum(b - a for a, b in spans)
    # position p attends p + 1 keys: sum over [a, b) of (p + 1)
    keys = sum((b * (b + 1) - a * (a + 1)) // 2 for a, b in spans)
    return per * n + attn_flops(model, 1) * keys \
        + 2 * head_params(model) * len(spans)


def paged_decode_attention_cost(conf: dict, keys: int,
                                kv_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of the decode attention kernel over ``keys`` cached
    positions summed over rows, all layers: 4·Hq·D FLOPs per key, and its K
    and V rows read once per layer."""
    m = dims(conf["model"])
    flops = 4 * m["hq"] * m["hd"] * keys * m["layers"]
    nbytes = 2 * m["hkv"] * m["hd"] * kv_bytes * keys * m["layers"]
    return flops, nbytes
