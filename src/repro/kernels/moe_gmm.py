"""MoE grouped expert matmul (SwiGLU FFN) — Pallas TPU kernel.

The expert-major bucket layout (E, C, D) from the permutation-gather
dispatch makes the expert FFN a *grouped* matmul: E independent
(C, D) x (D, F) problems. The TPU adaptation (vs. CUDA grouped-GEMM):

  * the expert axis is a parallel grid dimension — each program owns one
    (expert, C-tile, F-tile) cell, so no dynamic gather of weight pointers
    (the CUDA trick) is needed: BlockSpec index maps select the expert's
    weight tile directly;
  * tiles are MXU-shaped (BC x BK @ BK x BF), accumulated in f32 VMEM
    scratch over the sequential K axis;
  * the SwiGLU nonlinearity (silu(x@Wg) * (x@Wu)) fuses into the same
    kernel: both gate and up projections read the SAME x tile while it is
    resident in VMEM — one HBM pass over the (E, C, D) buckets instead of
    XLA's two.

Grid: (E, C/BC, F/BF, D/BK); K innermost/sequential carrying (acc_g, acc_u).
Output is the hidden activation h = silu(g)*u (E, C, F); the down
projection is a second call or plain XLA einsum (it is a regular matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat


def _swiglu_kernel(x_ref, wg_ref, wu_ref, h_ref, acc_g, acc_u, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[0]  # (BC, BK)
    acc_g[...] += jax.lax.dot_general(
        x, wg_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_u[...] += jax.lax.dot_general(
        x, wu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        g = acc_g[...]
        h_ref[0] = (g * jax.nn.sigmoid(g) * acc_u[...]).astype(h_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_k", "interpret"))
def moe_swiglu_hidden(
    x: jax.Array,      # (E, C, D) expert input buckets
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,    # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """h = silu(x @ w_gate) * (x @ w_up), grouped over experts. (E, C, F)."""
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    bk = min(block_k, d)

    def padto(a, axis, m):
        p = (-a.shape[axis]) % m
        if not p:
            return a
        w = [(0, 0)] * a.ndim
        w[axis] = (0, p)
        return jnp.pad(a, w)

    xp = padto(padto(x, 1, bc), 2, bk)
    wgp = padto(padto(w_gate, 1, bk), 2, bf)
    wup = padto(padto(w_up, 1, bk), 2, bf)
    cp, dp = xp.shape[1], xp.shape[2]
    fp = wgp.shape[2]
    nk = dp // bk
    grid = (e, cp // bc, fp // bf, nk)

    out = pl.pallas_call(
        functools.partial(_swiglu_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda ee, i, j, k: (ee, i, k)),
            pl.BlockSpec((1, bk, bf), lambda ee, i, j, k: (ee, k, j)),
            pl.BlockSpec((1, bk, bf), lambda ee, i, j, k: (ee, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ee, i, j, k: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cp, fp), x.dtype),
        scratch_shapes=[
            compat.vmem((bc, bf), jnp.float32),
            compat.vmem((bc, bf), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wgp, wup)
    return out[:, :c, :f]


def moe_mlp(expert_inputs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
            w_down: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Full expert FFN matching the `moe_mlp` hook ABI: the fused SwiGLU
    kernel + a grouped down-projection einsum."""
    h = moe_swiglu_hidden(expert_inputs, w_gate, w_up, interpret=interpret)
    out = jnp.einsum("ecf,efd->ecd", h, w_down,
                     preferred_element_type=jnp.float32)
    return out.astype(expert_inputs.dtype)
