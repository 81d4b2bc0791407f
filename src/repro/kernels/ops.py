"""Accelerated-API implementations + hook registration.

Three system-optimized provider tiers per API (DESIGN.md §1 — the paper's
"system-optimized libraries" bound by OCI-style hooks at deploy time; full
matrix in docs/kernel-portability.md):

  * ``xla-blocked`` — memory-bounded pure-JAX implementations (blocked /
    online-softmax attention, chunkwise mLSTM). These lower to clean HLO on
    any XLA backend, keep peak memory O(block) instead of O(S^2), and are
    what the multi-pod dry-run binds (Pallas cannot lower for the CPU
    stand-in devices; on real TPU metal the pallas-tpu tier wins instead).
  * ``pallas-interpret`` — the SAME hand-tiled Pallas kernels forced into
    interpret mode: the Pallas interpreter emulates the grid/BlockSpec/
    scratch machinery with pure-JAX ops, so the kernels' tiling logic runs
    (and is CI-exercised) on any backend, at emulation speed.
  * ``pallas-tpu`` — hand-tiled Pallas TPU kernels (flash_attention,
    decode_attention, rmsnorm, rglru scan, moe grouped matmul, chunked
    mLSTM), validated against kernels/ref.py oracles in interpret mode.

Priorities: pallas-tpu (20) > pallas-interpret (15) > xla-blocked (10) >
portable reference (0).

Each Pallas-backed tier registers a *probe* (core/hooks.py): a small
candidate kernel compiled and run exactly the way the tier would execute on
the target. ``bind(profile, probe=True)`` rejects tiers whose probe fails,
and the rejection is recorded in the binding's manifest.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import hooks
from repro.kernels import decode_attention as _dec_pallas
from repro.kernels import flash_attention as _fa_pallas
from repro.kernels import moe_gmm as _gmm_pallas
from repro.kernels import paged_attention as _paged_pallas
from repro.kernels import ref
from repro.kernels import rmsnorm as _rms_pallas

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blocked (memory-efficient) attention — pure JAX, O(bq*bk) live logits
# ---------------------------------------------------------------------------
def blocked_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    block_q: int = 256,
    block_k: int = 512,
) -> jax.Array:
    """Online-softmax attention in pure jnp: outer scan over query blocks
    (rematerialized in backward), inner scan over kv blocks carrying
    (m, l, acc). GQA is handled by head grouping — the kv heads are never
    materially expanded. Same ABI as kernels/ref.py::attention.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # may differ from d (MLA latent-space decode)
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else d**-0.5

    if sq * skv <= 2048 * 2048:
        # small problem: the plain oracle is cheaper than the scan machinery
        return ref.attention(
            q, k, v, causal=causal, window=window, scale=scale,
            logit_softcap=logit_softcap)

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk

    # (B, Hkv, G, S, D) layout: group dim keeps GQA unexpanded
    qt = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, d)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, Skv, D)
    vt = v.transpose(0, 2, 1, 3)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (sq + pad_q) // bq
    nk = (skv + pad_k) // bk
    offset = skv - sq  # suffix alignment of queries

    def q_block(i):
        qi = jax.lax.dynamic_slice_in_dim(qt, i * bq, bq, axis=3)
        qi = qi.astype(jnp.float32) * scale
        qpos = i * bq + jax.lax.iota(jnp.int32, bq) + offset  # (bq,)

        def kv_step(carry, j):
            m_prev, l_prev, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(kt, j * bk, bk, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(vt, j * bk, bk, axis=2)
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qi, kj.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            if logit_softcap is not None:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            kpos = j * bk + jax.lax.iota(jnp.int32, bk)
            mask = (kpos < skv)[None, :]
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[..., None])
            l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vj.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_cur, l_cur, acc), None

        m0 = jnp.full((b, hkv, g, bq), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk, dtype=jnp.int32))
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padded) rows
        return (acc / l[..., None]).astype(q.dtype)  # (B,Hkv,G,bq,Dv)

    # named_scope marks this region in HLO op_name metadata: the roofline
    # walker credits its score-matrix dots as VMEM-resident (the deployed
    # pallas-tpu tier is flash attention; see hlo_cost._KERNEL_REGION_RE)
    with jax.named_scope("fused_attention_kernel"):
        blocks = jax.lax.map(jax.checkpoint(q_block),
                             jnp.arange(nq, dtype=jnp.int32))
    # (nq, B, Hkv, G, bq, Dv) -> (B, Hq, Sq, Dv) -> (B, Sq, Hq, Dv)
    out = jnp.moveaxis(blocks, 0, 3).reshape(b, hkv * g, nq * bq, dv)
    return out[:, :, :sq].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Chunkwise-parallel mLSTM — pure JAX, O(C^2) live scores per chunk
# ---------------------------------------------------------------------------
def mlstm_chunkwise(
    q: jax.Array,  # (B, S, H, Dh)
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,  # (B, S, H) pre-activation
    f_gate: jax.Array,
    *,
    chunk: int = 256,
) -> jax.Array:
    """Chunkwise-parallel stabilized mLSTM, matching kernels/ref.py::mlstm.

    Sequential lax.scan over S/C chunks carrying the (C, n, m) matrix-memory
    state; inside a chunk the quadratic part is a (C x C) block — the same
    decomposition the official xLSTM kernels use, adapted to XLA (the Pallas
    TPU version lives in kernels/mlstm_chunk.py).
    """
    b, s, h, dh = q.shape
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        zf = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v = zf(q), zf(k), zf(v)
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)))
        # padded steps must not contribute: i = -inf, f = +inf (keep state)
        i_gate = i_gate.at[:, s:].set(-1e30)
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)),
                         constant_values=30.0)
    sp = s + pad
    nc = sp // c

    # (B, NC, C, H, Dh) chunked views, f32 compute
    ch = lambda a: a.reshape(b, nc, c, *a.shape[2:]).astype(jnp.float32)
    qc, kc, vc = ch(q) * dh**-0.5, ch(k), ch(v)
    ic, fc = ch(i_gate), ch(f_gate)
    log_f = jax.nn.log_sigmoid(fc)  # (B, NC, C, H)
    F = jnp.cumsum(log_f, axis=2)  # inclusive within-chunk prefix sums
    a_t = ic - F  # (B, NC, C, H)

    tpos = jnp.arange(c)[:, None]
    spos = jnp.arange(c)[None, :]
    causal = (spos <= tpos)[None, :, :, None]  # (1, C, C, 1)

    def chunk_step(carry, xs):
        C_prev, n_prev, m_prev = carry  # (B,H,Dh,Dh), (B,H,Dh), (B,H)
        qj, kj, vj, ij, Fj, aj = xs  # (B,C,H,Dh) x3, (B,C,H) x3
        # running stabilizer: M_t = max(cummax_s<=t (i_s - F_s), m_prev)
        M = jnp.maximum(jax.lax.cummax(aj, axis=1), m_prev[:, None, :])
        m_t = Fj + M  # (B,C,H) — the recurrent m_t
        # intra-chunk: D[t,s] = exp(i_s - F_s - M_t) for s<=t
        log_d = aj[:, None, :, :] - M[:, :, None, :]  # (B,T,S,H)
        d = jnp.where(causal, jnp.exp(log_d), 0.0)
        scores = jnp.einsum("bthd,bshd->btsh", qj, kj) * d
        num_intra = jnp.einsum("btsh,bshv->bthv", scores, vj)
        den_intra = jnp.sum(scores, axis=2)  # (B,T,H)
        # inter-chunk: coeff_t = exp(m_prev - M_t)
        w_in = jnp.exp(m_prev[:, None, :] - M)  # (B,C,H)
        num_inter = jnp.einsum("bthd,bhdv->bthv", qj, C_prev) * w_in[..., None]
        den_inter = jnp.einsum("bthd,bhd->bth", qj, n_prev) * w_in
        num = num_intra + num_inter
        den = den_intra + den_inter
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
        out = num / den[..., None]
        # end-of-chunk state at stabilizer m_T = F_C + M_C
        M_c = M[:, -1, :]  # (B,H)
        F_c = Fj[:, -1, :]
        w_state = jnp.exp(aj - M_c[:, None, :])  # (B,C,H): i_s - F_s - M_C
        C_new = jnp.exp(m_prev - M_c)[:, :, None, None] * C_prev + jnp.einsum(
            "bsh,bshd,bshv->bhdv", w_state, kj, vj)
        n_new = jnp.exp(m_prev - M_c)[:, :, None] * n_prev + jnp.einsum(
            "bsh,bshd->bhd", w_state, kj)
        m_new = F_c + M_c
        return (C_new, n_new, m_new), out

    C0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, h, dh), jnp.float32)
    m0 = jnp.full((b, h), _NEG_INF, jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (qc, kc, vc, ic, F, a_t))
    with jax.named_scope("fused_mlstm_kernel"):
        _, outs = jax.lax.scan(chunk_step, (C0, n0, m0), xs)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sp, h, dh)
    return out[:, :s].astype(q.dtype)



# ---------------------------------------------------------------------------
# Pallas wrappers (jit'd, ABI == ref)
# ---------------------------------------------------------------------------
def pallas_attention(q, k, v, *, causal=True, window=None, scale=None,
                     logit_softcap=None):
    return _fa_pallas.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        logit_softcap=logit_softcap)


def pallas_decode_attention(q, k_cache, v_cache, *, lengths=None, window=None,
                            scale=None, logit_softcap=None):
    return _dec_pallas.decode_attention(
        q, k_cache, v_cache, lengths=lengths, window=window, scale=scale,
        logit_softcap=logit_softcap)


# ---------------------------------------------------------------------------
# Interpret-tier wrappers (Pallas kernels pinned to the interpreter, so the
# hand-tiled grid/BlockSpec code runs on CPU/GPU hosts — and on CPU CI)
# ---------------------------------------------------------------------------
def interpret_attention(q, k, v, *, causal=True, window=None, scale=None,
                        logit_softcap=None):
    return _fa_pallas.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        logit_softcap=logit_softcap, interpret=True)


def interpret_decode_attention(q, k_cache, v_cache, *, lengths=None,
                               window=None, scale=None, logit_softcap=None):
    return _dec_pallas.decode_attention(
        q, k_cache, v_cache, lengths=lengths, window=window, scale=scale,
        logit_softcap=logit_softcap, interpret=True)


def pallas_paged_decode_attention(q, k_pool, v_pool, block_tables, *,
                                  lengths=None, window=None, scale=None,
                                  logit_softcap=None):
    return _paged_pallas.paged_decode_attention(
        q, k_pool, v_pool, block_tables, lengths=lengths, window=window,
        scale=scale, logit_softcap=logit_softcap)


def interpret_paged_decode_attention(q, k_pool, v_pool, block_tables, *,
                                     lengths=None, window=None, scale=None,
                                     logit_softcap=None):
    return _paged_pallas.paged_decode_attention(
        q, k_pool, v_pool, block_tables, lengths=lengths, window=window,
        scale=scale, logit_softcap=logit_softcap, interpret=True)


def interpret_rmsnorm(x, weight, *, eps=1e-6):
    return _rms_pallas.rmsnorm(x, weight, eps=eps, interpret=True)


def interpret_moe_mlp(expert_inputs, w_gate, w_up, w_down):
    return _gmm_pallas.moe_mlp(expert_inputs, w_gate, w_up, w_down,
                               interpret=True)


# ---------------------------------------------------------------------------
# Deploy-time probes: compile + run a small candidate kernel per tier, in the
# mode the tier executes on the target; a failure rejects the tier at bind
# time. Probe tiles are (8, 128) — the f32 minimum Mosaic tile — and the
# decode probes use B >= 2 rows and Hkv >= 2 KV heads, so per-row SMEM
# operands and the KV-head blocking meet the same Mosaic layout rules they
# meet at serving widths.
# ---------------------------------------------------------------------------
def _probe_args_attn():
    z = jnp.zeros((1, 8, 8, 128), jnp.float32)
    return z, jnp.zeros((1, 8, 1, 128), jnp.float32), jnp.zeros(
        (1, 8, 1, 128), jnp.float32)


def _probe_flash(interpret):
    def probe(profile):
        q, k, v = _probe_args_attn()
        _fa_pallas.flash_attention(
            q, k, v, block_q=8, block_k=8, interpret=interpret
        ).block_until_ready()
    return probe


def _probe_decode(interpret):
    def probe(profile):
        q = jnp.zeros((2, 8, 128), jnp.float32)
        kc = jnp.zeros((2, 16, 2, 128), jnp.float32)
        lengths = jnp.array([5, 16], jnp.int32)
        _dec_pallas.decode_attention(
            q, kc, kc, lengths=lengths, block_k=8, interpret=interpret
        ).block_until_ready()
    return probe


def _probe_paged_decode(interpret):
    def probe(profile):
        q = jnp.zeros((2, 8, 128), jnp.float32)
        pool = jnp.zeros((5, 8, 2 * 128), jnp.float32)  # (P, page, Hkv*D)
        bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
        lengths = jnp.array([5, 16], jnp.int32)
        _paged_pallas.paged_decode_attention(
            q, pool, pool, bt, lengths=lengths, interpret=interpret
        ).block_until_ready()
    return probe


def _probe_rmsnorm(interpret):
    def probe(profile):
        x = jnp.zeros((8, 128), jnp.float32)
        w = jnp.zeros((128,), jnp.float32)
        _rms_pallas.rmsnorm(
            x, w, block_rows=8, interpret=interpret).block_until_ready()
    return probe


def _probe_moe(interpret):
    def probe(profile):
        x = jnp.zeros((1, 8, 128), jnp.float32)
        w = jnp.zeros((1, 128, 128), jnp.float32)
        _gmm_pallas.moe_swiglu_hidden(
            x, w, w, block_c=8, block_f=128, block_k=128, interpret=interpret
        ).block_until_ready()
    return probe


def _probe_blocked(profile):
    q, k, v = _probe_args_attn()
    blocked_attention(q, k, v, block_q=8, block_k=8).block_until_ready()


# the pallas-tpu tier always compiles through Mosaic; pallas-interpret is
# the only tier that runs the kernels in the interpreter
_TPU_MODE = False
_INTERP_MODE = True


# ---------------------------------------------------------------------------
# Registration
# ---------------------------------------------------------------------------
def _is_tpu(profile: Any) -> bool:
    return getattr(profile, "chip", "").startswith("tpu") and profile.supports(
        "pallas-tpu")


def _is_interp(profile: Any) -> bool:
    return profile.supports("pallas-interpret")


def _is_xla(profile: Any) -> bool:
    return profile.supports("xla-blocked") or _is_tpu(profile) or _is_interp(
        profile)


def _register() -> None:
    reg = hooks.register_impl
    impls = {n for api in hooks.list_apis()
             for n in hooks.available_impls(api)}
    if "xla-blocked" in impls:
        return  # idempotent
    reg("attention", "xla-blocked", blocked_attention,
        supports=_is_xla, priority=10, probe=_probe_blocked)
    reg("attention", "pallas-interpret", interpret_attention,
        supports=_is_interp, priority=15, probe=_probe_flash(_INTERP_MODE))
    reg("attention", "pallas-tpu", pallas_attention,
        supports=_is_tpu, priority=20, probe=_probe_flash(_TPU_MODE))
    reg("decode_attention", "pallas-interpret", interpret_decode_attention,
        supports=_is_interp, priority=15, probe=_probe_decode(_INTERP_MODE))
    reg("decode_attention", "pallas-tpu", pallas_decode_attention,
        supports=_is_tpu, priority=20, probe=_probe_decode(_TPU_MODE))
    reg("paged_decode_attention", "pallas-interpret",
        interpret_paged_decode_attention, supports=_is_interp, priority=15,
        probe=_probe_paged_decode(_INTERP_MODE))
    reg("paged_decode_attention", "pallas-tpu", pallas_paged_decode_attention,
        supports=_is_tpu, priority=20, probe=_probe_paged_decode(_TPU_MODE))
    reg("mlstm", "xla-blocked", mlstm_chunkwise,
        supports=_is_xla, priority=10)
    reg("rmsnorm", "pallas-interpret", interpret_rmsnorm,
        supports=_is_interp, priority=15, probe=_probe_rmsnorm(_INTERP_MODE))
    reg("rmsnorm", "pallas-tpu", _rms_pallas.rmsnorm,
        supports=_is_tpu, priority=20, probe=_probe_rmsnorm(_TPU_MODE))
    reg("moe_mlp", "pallas-interpret", interpret_moe_mlp,
        supports=_is_interp, priority=15, probe=_probe_moe(_INTERP_MODE))
    reg("moe_mlp", "pallas-tpu", _gmm_pallas.moe_mlp,
        supports=_is_tpu, priority=20, probe=_probe_moe(_TPU_MODE))


_register()
