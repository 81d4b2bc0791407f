"""Plain float32 forward of a Qwen2-family decoder (arXiv:2407.10671).

Follows the published description: pre-norm residual blocks of RMSNorm,
grouped-query attention with biased Q/K/V projections and rotary positions
(rotate-half, base ``rope_theta``), and a SwiGLU MLP; a final RMSNorm and
the LM head, tied to the embedding where ``tie_word_embeddings`` is set.
Departures, each forced by the benchmark and none by the model:

* weights are random, from the family's layout
  (``bench/families/qwen2.py``); the served values (bfloat16)
  are widened to float32 here, so the reference and the program hold the
  same numbers;
* RMSNorm gains are stored as ``1 + delta`` (the benchmark makes ``delta``);
* sequences are right-padded to one length so that one program serves every
  sequence; causality keeps the pad out of every real position;
* logits are computed only at the positions asked for.

Every matrix product runs at ``Precision.HIGHEST``. ``fp8=True`` is the
control: every weight product takes float8 (e4m3) operands, scaled per row
of the activations and per column of the weights, and accumulates in f32.
Layers run one at a time, so a model whose float32 weights exceed the chip
still fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 512


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    if fp8:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.dot(a, w, precision=HIGHEST)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, w, *, m, fp8):
    """One decoder layer over one sequence x: (T, d) float32."""
    m = dict(m)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t = x.shape[0]
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    g = hq // hkv
    h = _rms(x, 1.0 + w["ln1"], m["eps"])
    q = _rope((_mm(h, w["q_w"], fp8) + w["q_b"]).reshape(t, hq, hd),
              m["theta"])
    k = _rope((_mm(h, w["k_w"], fp8) + w["k_b"]).reshape(t, hkv, hd),
              m["theta"])
    v = (_mm(h, w["v_w"], fp8) + w["v_b"]).reshape(t, hkv, hd)
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, hkv, g, hd)
    kpos = jnp.arange(t)

    def block(args):
        i, qq = args
        s = jnp.einsum("qkgd,tkd->kgqt", qq, k, precision=HIGHEST)
        s = s * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    x = x + _mm(o.reshape(t, hq * hd), w["o_w"], fp8)
    h = _rms(x, 1.0 + w["ln2"], m["eps"])
    act = jax.nn.silu(_mm(h, w["gate_w"], fp8)) * _mm(h, w["up_w"], fp8)
    return x + _mm(act, w["down_w"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, head, *, eps, fp8):
    x = _rms(x, 1.0 + gain.astype(jnp.float32), eps)
    return _mm(x, head.astype(jnp.float32), fp8)


class Reference:
    """The reference model of one configuration file and seed."""

    def __init__(self, conf: dict, seed: int, *, fp8: bool = False):
        # the family module imports this one
        from bench.families import qwen2 as layout

        model = conf["model"]
        self.dtype = jnp.dtype(model.get("torch_dtype", "bfloat16"))
        self.key = weights.seed_key(seed)
        self.fp8 = fp8
        self.dims = d = layout.dims(model)
        self.m = {"hq": d["hq"], "hkv": d["hkv"], "hd": d["hd"],
                  "eps": float(model["rms_norm_eps"]),
                  "theta": float(model["rope_theta"])}
        self._layer_w = jax.jit(
            lambda key, i: layout.layer_tensors(key, model, i, self.dtype))
        self._top = jax.jit(
            lambda key, name: layout.top_tensor(key, model, name,
                                                self.dtype),
            static_argnums=1)

    def logits(self, seqs: list[np.ndarray], want: list[np.ndarray],
               pad_to: int) -> list[jax.Array]:
        """Logits (f32, on the device) at positions ``want[i]`` of each
        token sequence ``seqs[i]``, every sequence padded to ``pad_to``
        (a multiple of 512)."""
        if pad_to % Q_BLOCK:
            raise ValueError(f"pad_to {pad_to} is not a multiple of "
                             f"{Q_BLOCK}")
        m = self.m
        mkey = tuple(sorted(m.items()))
        embed = self._top(self.key, "embed")
        xs = []
        for s in seqs:
            toks = np.zeros((pad_to,), np.int32)
            toks[: len(s)] = s
            xs.append(embed[jnp.asarray(toks)].astype(jnp.float32))
        tied = self.dims["tied"]
        if not tied:
            del embed
        for i in range(self.dims["layers"]):
            w = self._layer_w(self.key, i)
            xs = [_layer(x, w, m=mkey, fp8=self.fp8) for x in xs]
            del w
        head = embed.T if tied else self._top(self.key, "head")
        gain = self._top(self.key, "final_ln")
        out = []
        for x, p in zip(xs, want):
            # rows padded to a power of two: few shapes, few compiles
            rows = np.zeros((1 << max(len(p) - 1, 0).bit_length(),), np.int32)
            rows[: len(p)] = p
            out.append(_head(x[jnp.asarray(rows)], gain, head, eps=m["eps"],
                             fp8=self.fp8)[: len(p)])
        return out
