"""What the benchmark hands the program: the seeded weights in the
program's own tree, from the configuration's family, and the serving
container's geometry.

``make_params`` checks the family's layout against ``jax.eval_shape`` of
the program's ``init_model``, so a change of layout fails here instead of
serving wrong weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights


def make_params(family, seed: int, conf: dict, cfg):
    """The whole weight tree on the device, in the served dtype, from one
    jitted call. Raises if the program's layout differs."""
    from repro.models import transformer

    dtype = jnp.dtype(cfg.param_dtype)
    want = jax.eval_shape(lambda: transformer.init_model(jax.random.key(0),
                                                         cfg))
    make = jax.jit(lambda k: family.program_tree(k, conf, dtype))
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
