"""Seeded weights of a Qwen2-family model, made by the benchmark.

The benchmark, not the program, owns the weights: the plain reference
regenerates every tensor from the same seed, layer by layer, and so takes
nothing the program has made. Each tensor has its own key,
``fold_in(fold_in(seed_key, tensor id), layer)``, so one layer can be made
alone and equals the same layer made inside the stacked tree.

Matrices are stored ``(in, out)``, N(0, 1/d_in), so every block adds
unit-sized updates. The embedding is N(0, 0.02^2), the configs'
``initializer_range``: with unit-sized rows a tied head would score the
current token far above every other (its logit is the row's squared norm),
and greedy decoding would only repeat it. RMSNorm gains are ``1 + delta``;
the tensor made here is ``delta``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# tensor id -> (shape from the config, kind); the ids are part of the
# yardstick: renumbering them changes every weight of every cell
LAYER_TENSORS = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w",
                 "gate_w", "up_w", "down_w", "ln1", "ln2")
TOP_TENSORS = ("embed", "head", "final_ln")
EMBED_STD = 0.02
BIAS_STD = 0.1
GAIN_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number up to 63 bits: the low 31 bits seed it,
    the rest are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


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


def _make(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_w") or name == "head":
        z = z * shape[0] ** -0.5
    elif name == "embed":
        z = z * EMBED_STD
    elif name.endswith("_b"):
        z = z * BIAS_STD
    elif name.startswith("ln") or name == "final_ln":
        z = z * GAIN_STD
    return z.astype(dtype)


def layer_tensors(key, model: dict, layer, dtype) -> dict[str, jax.Array]:
    """Every tensor of one layer (``layer`` may be traced)."""
    out = {}
    for i, (name, shape) in enumerate(layer_shapes(model).items()):
        k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
        out[name] = _make(k, name, shape, dtype)
    return out


def top_tensor(key, model: dict, name: str, dtype) -> jax.Array:
    m = dims(model)
    shape = {"embed": (m["v"], m["d"]), "head": (m["d"], m["v"]),
             "final_ln": (m["d"],)}[name]
    i = len(LAYER_TENSORS) + TOP_TENSORS.index(name)
    return _make(jax.random.fold_in(key, i), name, shape, dtype)
