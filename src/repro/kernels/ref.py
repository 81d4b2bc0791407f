"""Pure-jnp reference oracles for every accelerated API.

These are simultaneously:
  * the *portable* implementation each XaaS hook falls back to (the paper's
    lowest-common-denominator build that must run on any provider), and
  * the oracle every Pallas TPU kernel is validated against (allclose sweeps
    in tests/).

All numerics that matter (softmax, recurrences, norms) run in float32
regardless of input dtype and cast back, so portable and specialized paths
share one ABI contract: "inputs dtype X -> output dtype X, accumulation f32".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hooks

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last axis. x: (..., D), weight: (D,)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# matmul (the BLAS hook)
# ---------------------------------------------------------------------------
def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (..., K), w: (K, N) -> (..., N); f32 accumulation."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (training / prefill)
# ---------------------------------------------------------------------------
def _gqa_expand(k: jax.Array, n_q_heads: int) -> jax.Array:
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating each kv head Hq/Hkv times."""
    b, s, hkv, d = k.shape
    if hkv == n_q_heads:
        return k
    group = n_q_heads // hkv
    return jnp.repeat(k, group, axis=2)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
) -> jax.Array:
    """Multi-head attention with GQA/MQA via head broadcast.

    q: (B, Sq, Hq, Dh); k, v: (B, Skv, Hkv, Dh), Hq % Hkv == 0.
    window: attend only to the last `window` positions (local attention).
    Query position i is aligned to key position i + (Skv - Sq) (suffix align).
    Returns (B, Sq, Hq, Dh) in q.dtype.
    """
    b, sq, hq, dh = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else dh**-0.5
    kx = _gqa_expand(k, hq).astype(jnp.float32)
    vx = _gqa_expand(v, hq).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kx) * scale
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vx)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (single query token against a KV cache)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    *,
    lengths: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
) -> jax.Array:
    """One-token decode attention.

    q: (B, Hq, Dh); k_cache, v_cache: (B, S, Hkv, Dh);
    lengths: (B,) int32 — number of valid cache entries (current token is the
    last valid one). Positions >= length are masked. window limits attention
    to the trailing `window` valid positions.
    Returns (B, Hq, Dh).
    """
    b, hq, dh = q.shape
    s = k_cache.shape[1]
    scale = scale if scale is not None else dh**-0.5
    kx = _gqa_expand(k_cache, hq).astype(jnp.float32)
    vx = _gqa_expand(v_cache, hq).astype(jnp.float32)
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kx) * scale
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    kpos = jnp.arange(s)[None, :]
    if lengths is None:
        lengths = jnp.full((b,), s, dtype=jnp.int32)
    mask = kpos < lengths[:, None]
    if window is not None:
        mask &= kpos >= (lengths[:, None] - window)
    logits = jnp.where(mask[:, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, vx)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# chunk attention (suffix-continuation prefill against a KV cache)
# ---------------------------------------------------------------------------
def chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    *,
    positions: jax.Array,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
) -> jax.Array:
    """Multi-token continuation attention: a chunk of queries at absolute
    per-row ``positions`` attends a full KV cache (prefix entries restored
    from a prefix cache plus the chunk's own entries already scattered in).

    q: (B, Sq, Hq, Dh); k_cache, v_cache: (B, L, Hkv, Dh);
    positions: (B, Sq) int32 absolute position of each query.
    Cache slot j is visible to query i iff j <= positions[b, i]
    (causality over the whole cache, not just the chunk); window limits
    attention to the trailing `window` positions. Returns (B, Sq, Hq, Dh).
    """
    b, sq, hq, dh = q.shape
    lkv = k_cache.shape[1]
    scale = scale if scale is not None else dh**-0.5
    kx = _gqa_expand(k_cache, hq).astype(jnp.float32)
    vx = _gqa_expand(v_cache, hq).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kx) * scale
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    kpos = jnp.arange(lkv)[None, None, :]
    qpos = positions[:, :, None]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[:, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vx)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# paged attention (KV in a shared physical page pool, per-row block tables)
# ---------------------------------------------------------------------------
def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize per-row logical caches from a physical page pool.

    pool: (P, page, ...) fixed-size pages shared by every row;
    block_tables: (B, N) int32 — physical page id of row b's logical page j.
    Returns (B, N*page, ...): row b's logical cache in position order. With
    N*page == max_len this is bit-for-bit the contiguous (B, max_len, ...)
    cache the slot engine holds (unallocated table entries point at the
    reserved null page 0, whose garbage sits beyond `lengths` and is masked
    exactly like the slot cache's stale suffix).
    """
    g = pool[block_tables]  # (B, N, page, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def _gather_heads(pool: jax.Array, block_tables: jax.Array,
                  d: int) -> jax.Array:
    """A lane-dense (P, page, Hkv*D) pool's rows as (B, N*page, Hkv, D)
    caches. Only the gathered rows are split into heads, never the whole
    pool, so no program re-lays a pool to read it."""
    g = gather_pages(pool, block_tables)
    return g.reshape(g.shape[:2] + (g.shape[2] // d, d))


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    *,
    lengths: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
) -> jax.Array:
    """One-token decode attention over a paged KV pool.

    q: (B, Hq, Dh); k_pool, v_pool: (P, page, Hkv*Dh), lane-dense: KV head
    h of a position at lanes [h*Dh, (h+1)*Dh), so a page of Hkv*Dh = 128
    bf16 lanes is one unpadded (16, 128) tile; block_tables: (B, N) int32.
    The portable tier gathers each row's pages, splits heads on the gathered
    cache and runs the contiguous oracle, so it is byte-identical to the
    slot engine's reference path — the parity anchor every paged tier is
    validated against.
    """
    d = q.shape[-1]
    k_cache = _gather_heads(k_pool, block_tables, d)
    v_cache = _gather_heads(v_pool, block_tables, d)
    return decode_attention(
        q, k_cache, v_cache, lengths=lengths, window=window, scale=scale,
        logit_softcap=logit_softcap)


def paged_chunk_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    *,
    positions: jax.Array,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
) -> jax.Array:
    """Chunked-prefill attention over a paged KV pool: q (B, Sq, Hq, Dh) at
    absolute ``positions`` (B, Sq) attends the gathered logical caches (the
    chunk's own entries already scattered into the pool). Pools are
    (P, page, Hkv*Dh), laid out as for :func:`paged_decode_attention`."""
    d = q.shape[-1]
    k_cache = _gather_heads(k_pool, block_tables, d)
    v_cache = _gather_heads(v_pool, block_tables, d)
    return chunk_attention(
        q, k_cache, v_cache, positions=positions, window=window, scale=scale,
        logit_softcap=logit_softcap)


# ---------------------------------------------------------------------------
# first-order linear recurrence:  h_t = a_t * h_{t-1} + x_t
# ---------------------------------------------------------------------------
def linear_recurrence(
    a: jax.Array, x: jax.Array, *, h0: jax.Array | None = None, axis: int = 1
) -> jax.Array:
    """Associative-scan linear recurrence along `axis` (default: time axis of
    (B, S, D) inputs). Returns all h_t, same shape as x, x.dtype."""
    af = a.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if h0 is not None:
        # fold h0 into the first step: h_1 = a_1 * h0 + x_1
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(0, 1)
        first = tuple(idx)
        xf = xf.at[first].add(af[first] * jnp.expand_dims(h0.astype(jnp.float32), axis))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (af, xf), axis=axis)
    return h.astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE expert MLP over capacity-bucketed expert inputs (GShard layout)
# ---------------------------------------------------------------------------
def moe_mlp(
    expert_inputs: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
) -> jax.Array:
    """SwiGLU expert FFN applied per expert bucket.

    expert_inputs: (E, C, D); w_gate, w_up: (E, D, F); w_down: (E, F, D).
    Returns (E, C, D) in input dtype.
    """
    g = jnp.einsum("ecd,edf->ecf", expert_inputs, w_gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", expert_inputs, w_up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(expert_inputs.dtype)
    out = jnp.einsum("ecf,efd->ecd", h, w_down, preferred_element_type=jnp.float32)
    return out.astype(expert_inputs.dtype)


# ---------------------------------------------------------------------------
# mLSTM chunkwise-parallel form (xLSTM) — full parallel O(S^2) oracle
# ---------------------------------------------------------------------------
def mlstm(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,
    f_gate: jax.Array,
) -> jax.Array:
    """Stabilized parallel mLSTM (xLSTM eq. 20-26).

    q, k, v: (B, S, H, Dh); i_gate, f_gate: (B, S, H) pre-activation.
    Returns (B, S, H, Dh) in q.dtype.
    """
    b, s, h, dh = q.shape
    qf = q.astype(jnp.float32) * dh**-0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))  # (B,S,H)
    log_f_cum = jnp.cumsum(log_f, axis=1)  # prefix sums inclusive
    # log decay D[t, s'] = (logfcum_t - logfcum_s') + i_s'   for s' <= t
    i_f32 = i_gate.astype(jnp.float32)
    log_d = (
        log_f_cum[:, :, None, :] - log_f_cum[:, None, :, :] + i_f32[:, None, :, :]
    )  # (B, T, S, H)
    tpos = jnp.arange(s)[:, None]
    spos = jnp.arange(s)[None, :]
    causal = (spos <= tpos)[None, :, :, None]
    log_d = jnp.where(causal, log_d, _NEG_INF)
    m = jnp.max(log_d, axis=2, keepdims=True)  # (B,T,1,H) row stabilizer
    d = jnp.exp(log_d - m)
    scores = jnp.einsum("bthd,bshd->btsh", qf, kf) * d
    denom = jnp.maximum(jnp.abs(jnp.sum(scores, axis=2)), jnp.exp(-m[:, :, 0, :]))
    out = jnp.einsum("btsh,bshd->bthd", scores, vf) / denom[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Registration: these references ARE the portable implementations.
# ---------------------------------------------------------------------------
def _register() -> None:
    if "attention" in hooks.list_apis():
        return  # idempotent under re-import
    hooks.register_api(
        "rmsnorm",
        "rmsnorm(x(...,D), weight(D,), *, eps) -> (...,D); f32 accumulation",
        rmsnorm,
    )
    hooks.register_api("matmul", "matmul(x(...,K), w(K,N)) -> (...,N); f32 acc", matmul)
    hooks.register_api(
        "attention",
        "attention(q(B,Sq,Hq,D), k(B,Skv,Hkv,D), v, *, causal, window, scale,"
        " logit_softcap) -> (B,Sq,Hq,D)",
        attention,
    )
    hooks.register_api(
        "decode_attention",
        "decode_attention(q(B,Hq,D), k_cache(B,S,Hkv,D), v_cache, *, lengths(B,),"
        " window, scale, logit_softcap) -> (B,Hq,D)",
        decode_attention,
    )
    hooks.register_api(
        "chunk_attention",
        "chunk_attention(q(B,Sq,Hq,D), k_cache(B,L,Hkv,D), v_cache, *,"
        " positions(B,Sq), window, scale, logit_softcap) -> (B,Sq,Hq,D)",
        chunk_attention,
    )
    hooks.register_api(
        "paged_decode_attention",
        "paged_decode_attention(q(B,Hq,D), k_pool(P,page,Hkv*D), v_pool,"
        " block_tables(B,N), *, lengths(B,), window, scale, logit_softcap)"
        " -> (B,Hq,D)",
        paged_decode_attention,
    )
    hooks.register_api(
        "paged_chunk_attention",
        "paged_chunk_attention(q(B,Sq,Hq,D), k_pool(P,page,Hkv*D), v_pool,"
        " block_tables(B,N), *, positions(B,Sq), window, scale,"
        " logit_softcap) -> (B,Sq,Hq,D)",
        paged_chunk_attention,
    )
    hooks.register_api(
        "linear_recurrence",
        "linear_recurrence(a(B,S,D), x(B,S,D), *, h0(B,D), axis) -> (B,S,D)",
        linear_recurrence,
    )
    hooks.register_api(
        "moe_mlp",
        "moe_mlp(expert_inputs(E,C,D), w_gate(E,D,F), w_up(E,D,F), w_down(E,F,D))"
        " -> (E,C,D); SwiGLU",
        moe_mlp,
    )
    hooks.register_api(
        "mlstm",
        "mlstm(q,k,v(B,S,H,D), i_gate(B,S,H), f_gate(B,S,H)) -> (B,S,H,D)",
        mlstm,
    )


_register()
