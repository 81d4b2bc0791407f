"""Paged decode attention (one query token vs a paged KV pool) — Pallas TPU.

vLLM-style PagedAttention adapted to the flash-decoding kernel in
``decode_attention.py``: the KV cache is no longer one contiguous
``(B, S, Hkv, D)`` region per batch row but a shared physical pool of
fixed-size pages ``(P, page, Hkv*D)``, and each row owns an int32 *block
table* mapping its logical page j to a physical page id. The kernel walks a
row's logical pages along a sequential grid axis; the page indirection
happens in the BlockSpec index map, which reads the scalar-prefetched block
table from SMEM — so the DMA engine streams exactly the pages the row owns,
in logical order, and the online-softmax recurrence is unchanged from the
contiguous kernel.

Grid: (B, N) with N = pool pages per row (block-table width); logical page
j covers absolute positions [j*page, (j+1)*page). The pool is lane-dense:
KV head h of a position sits at lanes [h*D, (h+1)*D) of the minor axis, so
one grid step streams a whole ``(1, page, Hkv*D)`` page (for qwen2-0.5b,
Hkv*D = 128 and a bf16 page is one unpadded (16, 128) tile) and each KV
head's (page, D) lane slice is read out of it in VMEM. With (Hkv, D) as the
two minor dims instead, a page of D = 64 fills half of every padded tile;
XLA then lays the pool out another way than the kernel's operand, and
re-lays a whole layer's pool around every call. Pages entirely
past a row's ``length`` are skipped block-level (``pl.when`` — no compute
for the unallocated suffix, whose table entries point at the reserved null
page 0). Window (local attention) masks positions < length - window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

_NEG_INF = -1e30


def _paged_decode_kernel(
    len_ref,  # SMEM (B,)    valid lengths, scalar-prefetched
    bt_ref,  # SMEM (B, N)   block tables, scalar-prefetched
    q_ref,  # (1, Hkv, G, D)
    k_ref,  # (1, page, Hkv*D)  the physical page the index map gathered
    v_ref,
    o_ref,  # (1, Hkv, G, D)
    m_scr, l_scr, acc_scr,  # (Hkv, G), (Hkv, G), (Hkv, G, D)
    *, scale: float, window: int | None, softcap: float | None, page: int,
    hkv: int, d: int,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = j * page < length
    if window is not None:
        run = run & (j * page + page - 1 >= length - window)

    @pl.when(run)
    def _compute():
        for h in range(hkv):
            lanes = slice(h * d, (h + 1) * d)  # KV head h's lanes
            q = q_ref[0, h].astype(jnp.float32)  # (G, D)
            k = k_ref[0, :, lanes].astype(jnp.float32)  # (page, D)
            v = v_ref[0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, page)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos < length
            if window is not None:
                mask &= kpos >= length - window
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_scr[h]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[:, None])
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1)
            acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_cur

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "logit_softcap", "interpret"))
def paged_decode_attention(
    q: jax.Array,  # (B, Hq, D)
    k_pool: jax.Array,  # (P, page, Hkv*D) shared physical page pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, N) int32 physical page ids
    *,
    lengths: jax.Array | None = None,  # (B,) int32
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for the `paged_decode_attention` hook ABI (kernels/ref.py)."""
    b, hq, d = q.shape
    page = k_pool.shape[1]
    hkv = k_pool.shape[2] // d
    n = block_tables.shape[1]
    assert k_pool.shape[2] == hkv * d and hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else d**-0.5
    if lengths is None:
        lengths = jnp.full((b,), n * page, jnp.int32)

    qt = q.reshape(b, hkv, g, d)
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window,
        softcap=logit_softcap, page=page, hkv=hkv, d=d)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # lengths + block tables land in SMEM
            grid=(b, n),
            in_specs=[
                pl.BlockSpec(
                    (1, hkv, g, d), lambda b_, j, lens, bt: (b_, 0, 0, 0)),
                # the paging indirection: logical page j of row b_ is the
                # physical pool page the prefetched table names
                pl.BlockSpec(
                    (1, page, hkv * d),
                    lambda b_, j, lens, bt: (bt[b_, j], 0, 0)),
                pl.BlockSpec(
                    (1, page, hkv * d),
                    lambda b_, j, lens, bt: (bt[b_, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, hkv, g, d), lambda b_, j, lens, bt: (b_, 0, 0, 0)),
            scratch_shapes=[
                compat.vmem((hkv, g), jnp.float32),
                compat.vmem((hkv, g), jnp.float32),
                compat.vmem((hkv, g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), qt,
      k_pool, v_pool)

    return out.reshape(b, hq, d)
