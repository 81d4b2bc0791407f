"""Seeded weights, made by the benchmark: the seed's key and the law every
tensor is drawn by. Which tensors a model has, and the id each is keyed by,
is its family's (``bench/families/<model_type>.py``).

The benchmark, not the program, owns the weights: the plain reference
regenerates every tensor from the same seed, layer by layer, and so takes
nothing the program has made.

A tensor's name picks its law. Matrices (``*_w``, ``head``) are stored
``(in, out)``, N(0, 1/d_in), so every block adds unit-sized updates. The
embedding (``embed``) is N(0, 0.02^2), the configs' ``initializer_range``:
with unit-sized rows a tied head would score the current token far above
every other (its logit is the row's squared norm), and greedy decoding
would only repeat it. Biases (``*_b``) are N(0, 0.1^2). RMSNorm gains
(``ln*``, ``*_ln``) are ``1 + delta``; the tensor made here is ``delta``,
N(0, 0.1^2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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


def make(key, name: str, shape, dtype) -> jax.Array:
    """The tensor ``name`` of ``shape``, drawn from ``key`` by its law."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_w") or name == "head":
        z = z * shape[0] ** -0.5
    elif name == "embed":
        z = z * EMBED_STD
    elif name.endswith("_b"):
        z = z * BIAS_STD
    elif name.startswith("ln") or name.endswith("_ln"):
        z = z * GAIN_STD
    return z.astype(dtype)
