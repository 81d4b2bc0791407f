"""Global / local attention mixer with GQA-MQA, RoPE, and KV-cache decode."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hooks
from repro.distributed import sharding
from repro.models import layers


def init(key, cfg, *, window: int | None = None):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": layers.init_linear(ks[0], cfg.d_model, cfg.num_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "wk": layers.init_linear(ks[1], cfg.d_model, cfg.num_kv_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "wv": layers.init_linear(ks[2], cfg.d_model, cfg.num_kv_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "wo": layers.init_linear(ks[3], cfg.num_heads * hd, cfg.d_model, bias=False, dtype=dt),
    }


def _qkv(p, cfg, x, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = layers.linear(p["wq"], x).reshape(b, s, cfg.num_heads, hd)
    k = layers.linear(p["wk"], x).reshape(b, s, cfg.num_kv_heads, hd)
    v = layers.linear(p["wv"], x).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, positions, theta=cfg.rope_theta)
        k = layers.apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def _pad_heads(q, k, v):
    """§Perf: pad query heads so their count is a multiple of
    `__pad_heads__` (the model-axis size) — 56/40/24-head archs otherwise
    replicate the whole attention computation on every model rank.

    GQA mapping is preserved by padding WITHIN groups: each kv head's group
    grows g -> g' (zero q-heads interleaved per group), so original q head
    (group j, slot r) keeps attending to kv head j. MHA (g == 1) instead
    appends tiled kv heads + zero q heads (identity mapping preserved).
    Returns (q, k, v, unpad) where unpad(o) restores the original heads.
    """
    rules = sharding.current_rules() or {}
    mult = rules.get("__pad_heads__")
    b, s, hq, dh = q.shape
    ident = lambda o: o
    if not mult or hq % mult == 0:
        return q, k, v, ident
    hkv = k.shape[2]
    g = hq // hkv
    if g == 1:
        hq_pad = ((hq + mult - 1) // mult) * mult
        reps = (hq_pad + hkv - 1) // hkv
        k = jnp.tile(k, (1, 1, reps, 1))[:, :, :hq_pad]
        v = jnp.tile(v, (1, 1, reps, 1))[:, :, :hq_pad]
        q = jnp.pad(q, ((0, 0), (0, 0), (0, hq_pad - hq), (0, 0)))
        return q, k, v, (lambda o: o[:, :, :hq])
    # smallest g' >= g with hkv * g' divisible by mult
    gp = g
    while (hkv * gp) % mult:
        gp += 1
    qg = q.reshape(b, s, hkv, g, dh)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, gp - g), (0, 0)))
    q = qg.reshape(b, s, hkv * gp, dh)

    def unpad(o):
        og = o.reshape(*o.shape[:2], hkv, gp, o.shape[-1])
        return og[:, :, :, :g].reshape(*o.shape[:2], hkv * g, o.shape[-1])

    return q, k, v, unpad


def apply(p, cfg, x, positions, *, window: int | None = None):
    """Full-sequence attention (train / prefill). x: (B, S, D) pre-normed."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    q, k, v, unpad = _pad_heads(q, k, v)
    q = sharding.constraint(q, "batch", "seq", "heads", None)
    k = sharding.constraint(k, "batch", "seq", "kv_heads", None)
    v = sharding.constraint(v, "batch", "seq", "kv_heads", None)
    o = hooks.call(
        "attention", q, k, v, causal=True, window=window,
        logit_softcap=cfg.logit_softcap,
    )
    o = unpad(o)
    o = sharding.constraint(o, "batch", "seq", None, None)
    return layers.linear(p["wo"], o.reshape(b, s, -1))


def prefill(p, cfg, x, positions, max_len: int, *, window: int | None = None):
    """Full-prompt attention + KV-cache build. x: (B, S, D), S <= max_len.

    Returns (y (B,S,D), state with caches sized max_len)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    qp, kp, vp, unpad = _pad_heads(q, k, v)  # cache keeps the UNpadded k/v
    qp = sharding.constraint(qp, "batch", "seq", "heads", None)
    o = hooks.call(
        "attention", qp, kp, vp, causal=True, window=window,
        logit_softcap=cfg.logit_softcap,
    )
    o = unpad(o)
    y = layers.linear(p["wo"], o.reshape(b, s, -1))
    state = init_state(cfg, b, max_len, k.dtype)
    k_cache = jax.lax.dynamic_update_slice(state["k"], k, (0, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(state["v"], v, (0, 0, 0, 0))
    k_cache = sharding.constraint(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = sharding.constraint(v_cache, "batch", "kv_seq", "kv_heads", None)
    return y, {"k": k_cache, "v": v_cache}


def init_state(cfg, batch: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill_chunk(p, cfg, x, positions, state, start, lengths, *,
                  window: int | None = None):
    """Continue a prefill from per-row offset ``start``: the chunk's K/V are
    scattered into the existing cache at absolute positions and the chunk's
    queries attend the whole cache (restored prefix + chunk) with absolute
    causality — the suffix-only half of prefix-cache reuse.

    x: (B, Sc, D) pre-normed (right-padded chunk); positions: (B, Sc)
    absolute positions start + [0..Sc); lengths: (B,) total valid entries
    after the chunk (start + real chunk length). Pad rows (chunk index >=
    lengths - start) are dropped from the cache write and produce garbage
    outputs the caller ignores.
    """
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    max_len = state["k"].shape[1]
    valid = jnp.arange(s)[None, :] < (lengths - start)[:, None]
    idx = jnp.where(valid, positions, max_len)  # out-of-range pads -> dropped
    bidx = jnp.arange(b)[:, None]
    k_cache = state["k"].at[bidx, idx].set(k.astype(state["k"].dtype),
                                           mode="drop")
    v_cache = state["v"].at[bidx, idx].set(v.astype(state["v"].dtype),
                                           mode="drop")
    k_cache = sharding.constraint(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = sharding.constraint(v_cache, "batch", "kv_seq", "kv_heads", None)
    o = hooks.call(
        "chunk_attention", q, k_cache, v_cache, positions=positions,
        window=window, logit_softcap=cfg.logit_softcap,
    )
    y = layers.linear(p["wo"], o.reshape(b, s, -1))
    return y, {"k": k_cache, "v": v_cache}


def init_paged_state(cfg, num_pages: int, page_size: int, dtype):
    """Paged KV pool: ``num_pages`` fixed-size pages shared by every request
    (physical page 0 is the engine's reserved null page). Same tree structure
    as :func:`init_state`, with pools of (num_pages, page_size, Hkv*Dh): KV
    head h of a position at lanes [h*Dh, (h+1)*Dh). Lane-dense, a page is
    whole tiles, so XLA keeps the pool in the layout the paged kernel reads
    (kernels/paged_attention.py) and never re-lays it around a call."""
    hd = cfg.resolved_head_dim
    shape = (num_pages, page_size, cfg.num_kv_heads * hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill_chunk_paged(p, cfg, x, positions, state, block_tables, page_size,
                        start, lengths, *, window: int | None = None):
    """`prefill_chunk` against a paged KV pool: the chunk's K/V are scattered
    through the row's block table and the queries attend the gathered logical
    cache. Pad entries (chunk index >= lengths - start) are routed to the
    reserved null page 0 instead of dropped — same stale-beyond-the-length
    contract, no owned page is ever touched by a pad write.

    state: {"k","v"} (P, page, Hkv*Dh) pools; block_tables: (B, N) int32.
    """
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    n = block_tables.shape[1]
    # pads AND positions past the table's capacity route to the null page
    # (the contiguous path drops both via mode="drop"; spec verify chunks
    # near max_len can carry positions >= n*page_size)
    valid = (jnp.arange(s)[None, :] < (lengths - start)[:, None]) \
        & (positions < n * page_size)
    page_idx = jnp.clip(positions // page_size, 0, n - 1)
    phys = jnp.take_along_axis(block_tables, page_idx, axis=1)
    phys = jnp.where(valid, phys, 0)
    offset = positions % page_size
    k_pool = state["k"].at[phys, offset].set(
        k.reshape(b, s, -1).astype(state["k"].dtype))
    v_pool = state["v"].at[phys, offset].set(
        v.reshape(b, s, -1).astype(state["v"].dtype))
    o = hooks.call(
        "paged_chunk_attention", q, k_pool, v_pool, block_tables,
        positions=positions, window=window, logit_softcap=cfg.logit_softcap,
    )
    y = layers.linear(p["wo"], o.reshape(b, s, -1))
    return y, {"k": k_pool, "v": v_pool}


def decode_paged(p, cfg, x, state, block_tables, page_size, lengths, *,
                 window: int | None = None):
    """Single-token decode against a paged KV pool. Rows with lengths == 0
    (empty slots, rows still prefilling) write to the reserved null page 0;
    active rows write at index lengths-1 inside their own last page, which
    the engine guarantees is exclusively owned (copy-on-write happens before
    the step when a prefix-shared page would be written)."""
    b, _ = x.shape
    hd = cfg.resolved_head_dim
    pos = (lengths - 1).astype(jnp.int32)
    q = layers.linear(p["wq"], x).reshape(b, 1, cfg.num_heads, hd)
    k = layers.linear(p["wk"], x).reshape(b, 1, cfg.num_kv_heads, hd)
    v = layers.linear(p["wv"], x).reshape(b, 1, cfg.num_kv_heads, hd)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, pos[:, None], theta=cfg.rope_theta)
        k = layers.apply_rope(k, pos[:, None], theta=cfg.rope_theta)
    n = block_tables.shape[1]
    safe = jnp.maximum(pos, 0)
    page_idx = jnp.clip(safe // page_size, 0, n - 1)
    phys = jnp.take_along_axis(block_tables, page_idx[:, None], axis=1)[:, 0]
    phys = jnp.where(lengths > 0, phys, 0)
    offset = safe % page_size
    k_pool = state["k"].at[phys, offset].set(
        k.reshape(b, -1).astype(state["k"].dtype))
    v_pool = state["v"].at[phys, offset].set(
        v.reshape(b, -1).astype(state["v"].dtype))
    o = hooks.call(
        "paged_decode_attention", q[:, 0], k_pool, v_pool, block_tables,
        lengths=lengths, window=window, logit_softcap=cfg.logit_softcap,
    )
    y = layers.linear(p["wo"], o.reshape(b, -1))
    return y, {"k": k_pool, "v": v_pool}


def decode(p, cfg, x, state, lengths, *, window: int | None = None):
    """Single-token decode. x: (B, D); lengths: (B,) valid entries *including*
    the current token, which is written at index lengths-1."""
    b, _ = x.shape
    hd = cfg.resolved_head_dim
    pos = (lengths - 1).astype(jnp.int32)
    q = layers.linear(p["wq"], x).reshape(b, 1, cfg.num_heads, hd)
    k = layers.linear(p["wk"], x).reshape(b, 1, cfg.num_kv_heads, hd)
    v = layers.linear(p["wv"], x).reshape(b, 1, cfg.num_kv_heads, hd)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, pos[:, None], theta=cfg.rope_theta)
        k = layers.apply_rope(k, pos[:, None], theta=cfg.rope_theta)
    bidx = jnp.arange(b)
    k_cache = state["k"].at[bidx, pos].set(k[:, 0].astype(state["k"].dtype))
    v_cache = state["v"].at[bidx, pos].set(v[:, 0].astype(state["v"].dtype))
    k_cache = sharding.constraint(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = sharding.constraint(v_cache, "batch", "kv_seq", "kv_heads", None)
    o = hooks.call(
        "decode_attention", q[:, 0], k_cache, v_cache, lengths=lengths,
        window=window, logit_softcap=cfg.logit_softcap,
    )
    y = layers.linear(p["wo"], o.reshape(b, -1))
    return y, {"k": k_cache, "v": v_cache}
