"""Decode attention (one query token vs KV cache) — Pallas TPU kernel.

Flash-decoding adapted to TPU: at decode, the q "matrix" is a single token
per (batch, kv-head) — compute is trivially memory-bound on streaming the KV
cache HBM->VMEM. The kernel therefore:

  * processes all G = Hq/Hkv grouped query heads of one kv head together
    (one (G, D) q tile amortizes each streamed (BK, D) kv tile — the GQA
    arithmetic-intensity multiplier, which is the reason GQA exists),
  * walks the cache in (BK, D) blocks along a sequential grid axis with
    online-softmax scratch (same recurrence as prefill flash),
  * reads per-row valid `lengths` (scalar-prefetched into SMEM) and masks
    the tail block, and
    skips blocks entirely past `length` (pl.when — no HBM traffic for the
    unused cache suffix of short rows... the *block-level* early exit).

Grid: (B, Hkv, S/BK), kv axis sequential. Window (local attention) masks
positions < length - window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

_NEG_INF = -1e30
DEFAULT_BK = 256


def _decode_kernel(
    len_ref,  # SMEM (B,)   valid lengths, scalar-prefetched
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, 1, BK, D)
    v_ref,
    o_ref,  # (1, 1, G, D)
    m_scr, l_scr, acc_scr,
    *, scale: float, window: int | None, softcap: float | None, bk: int,
):
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = j * bk < length
    if window is not None:
        run = run & (j * bk + bk - 1 >= length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, BK)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window is not None:
            mask &= kpos >= length - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "logit_softcap", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, Hq, D)
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,
    *,
    lengths: jax.Array | None = None,  # (B,) int32
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for the `decode_attention` hook ABI (see kernels/ref.py)."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else d**-0.5
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)

    bk = min(block_k, max(8, 1 << (s - 1).bit_length()))
    pad = (-s) % bk

    qt = q.reshape(b, hkv, g, d)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, Hkv, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sp = s + pad

    grid = (b, hkv, sp // bk)
    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=logit_softcap, bk=bk)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # lengths land in SMEM
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b_, h, j, ln: (b_, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, ln: (b_, h, j, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, ln: (b_, h, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, d), lambda b_, h, j, ln: (b_, h, 0, 0)),
            scratch_shapes=[
                compat.vmem((g,), jnp.float32),
                compat.vmem((g,), jnp.float32),
                compat.vmem((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt)

    return out.reshape(b, hq, d)
