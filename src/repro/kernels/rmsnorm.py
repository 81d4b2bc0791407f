"""RMSNorm — Pallas TPU kernel.

A bandwidth-bound elementwise+reduce op: the win over unfused XLA is a
single HBM pass (read x, write y) with the f32 mean-of-squares computed in
VMEM. Rows are tiled (BR, D): one block holds BR full rows so the reduction
never crosses blocks; D is the full feature dim (model-parallel shards pass
their local D — RMSNorm is row-wise so sharded features need a psum OUTSIDE
the kernel; the hook keeps feature dim unsharded per the ABI).

Grid: (rows/BR,). VMEM per block: BR*D*(2 bytes bf16 in + 4 bytes f32
scratch) — BR chosen so a (BR, D) f32 tile fits comfortably (<= ~4 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (BR, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)  # (D,)
    o_ref[...] = (y * (1.0 + w[None, :])).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """Drop-in for the `rmsnorm` hook ABI (see kernels/ref.py)."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    rows = 1
    for n in lead:
        rows *= n
    x2 = x.reshape(rows, d)
    # block size: keep the f32 working tile under ~4 MB of VMEM
    br = max(8, min(block_rows, rows, (4 << 20) // max(4 * d, 1)))
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
    )(x2, weight)
    return out[:rows].reshape(*lead, d)
