"""Flash attention (training/prefill) — Pallas TPU kernel.

TPU-native adaptation of the GPU flash-attention insight (DESIGN.md §1): the
point that transfers is *online-softmax tiling so the S×S score matrix never
touches HBM*; what changes for TPU is the blocking. Blocks are MXU-shaped
((128, head_dim) q tiles against (BK, head_dim) kv tiles, BK a multiple of
128), scratch accumulators live in VMEM across the sequential kv grid axis,
and GQA is handled by an index map that points each q-head block at its kv
head — no repeated kv in HBM (the jnp oracle materializes the expansion; the
kernel never does).

Grid: (batch, q_heads, Sq/BQ, Skv/BK), kv axis innermost/sequential
("arbitrary") so the VMEM scratch (m, l, acc) carries across it. Causal
blocks strictly above the diagonal are skipped via pl.when (zero work, not
just masked). Local-attention windows additionally skip blocks entirely left
of the window.

Supports: causal (suffix-aligned, Sq <= Skv), sliding window, logit softcap
(gemma/granite-style), GQA/MQA. Masked/padded kv tail handled by masking
against the true Skv (wrapper pads to block multiples).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

_NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,  # blocks
    m_scr, l_scr, acc_scr,  # VMEM scratch carried over the kv axis
    *, scale: float, causal: bool, window: int | None,
    softcap: float | None, sq: int, skv: int, bq: int, bk: int,
):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this block's rows/cols (suffix-aligned queries)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (skv - sq)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: causal => kv block start beyond last q row is dead;
    # window => kv block entirely left of the window is dead
    last_q = i * bq + bq - 1 + (skv - sq)
    first_q = i * bq + (skv - sq)
    run = jnp.bool_(True)
    if causal:
        run = run & (j * bk <= last_q)
    if window is not None:
        run = run & (j * bk + bk - 1 > first_q - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos < skv  # padded kv tail
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]  # (BQ,)
        l_prev = l_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        # fully-masked rows: p == exp(-inf - m) -> 0; keep l from 0-div later
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur
        l_scr[...] = l_cur

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # dead rows (padding) -> 0 output
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "logit_softcap",
                     "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for the `attention` hook ABI (see kernels/ref.py)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d**-0.5

    bq = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (skv - 1).bit_length()))

    # layout: (B, H, S, D) so the head axis is a pure grid axis
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, bq)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bk)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bk)
    sq_p, skv_p = qt.shape[2], kt.shape[2]

    grid = (b, hq, sq_p // bq, skv_p // bk)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=logit_softcap, sq=sq, skv=skv, bq=bq, bk=bk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        scratch_shapes=[
            compat.vmem((bq,), jnp.float32),
            compat.vmem((bq,), jnp.float32),
            compat.vmem((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)

    return out[:, :, :sq].transpose(0, 2, 1, 3)
