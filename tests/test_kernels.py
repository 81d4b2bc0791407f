"""Per-kernel allclose sweeps vs the kernels/ref.py pure-jnp oracles
(assignment deliverable (c)): Pallas kernels in interpret mode, xla-blocked
implementations, shape x dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref

KEY = jax.random.key(42)


def _qkv(b, sq, skv, hq, hkv, d, dtype, dv=None):
    ks = jax.random.split(jax.random.fold_in(KEY, sq * 131 + skv * 7 + hq), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, dv or d), jnp.float32).astype(dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention (Pallas, interpret mode) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,skv,hq,hkv,d,causal,window,softcap",
    [
        (2, 128, 128, 4, 4, 32, True, None, None),
        (1, 128, 128, 4, 2, 64, True, None, None),   # GQA
        (1, 128, 128, 4, 1, 32, True, None, None),   # MQA
        (1, 128, 256, 2, 1, 32, True, None, None),   # suffix-aligned
        (1, 128, 128, 2, 1, 32, True, 64, None),     # sliding window
        (1, 128, 128, 2, 2, 32, True, None, 30.0),   # logit softcap
        (1, 100, 100, 2, 1, 32, True, None, None),   # non-multiple of block
        (1, 128, 128, 2, 2, 32, False, None, None),  # non-causal
    ],
)
def test_flash_attention_vs_ref(b, sq, skv, hq, hkv, d, causal, window,
                                softcap, dtype):
    q, k, v = _qkv(b, sq, skv, hq, hkv, d, dtype)
    want = ref.attention(q, k, v, causal=causal, window=window,
                         logit_softcap=softcap)
    got = fa.flash_attention(q, k, v, causal=causal, window=window,
                             logit_softcap=softcap, block_q=64, block_k=64,
                             interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# decode attention (Pallas, interpret) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,window",
    [
        (2, 256, 4, 4, 32, None),
        (2, 256, 8, 2, 64, None),   # GQA group 4
        (1, 256, 4, 1, 32, None),   # MQA
        (2, 256, 4, 1, 32, 64),     # sliding window
        (2, 100, 2, 1, 32, None),   # ragged cache length
    ],
)
def test_decode_attention_vs_ref(b, s, hq, hkv, d, window, dtype):
    q3, k, v = _qkv(b, 1, s, hq, hkv, d, dtype)
    q = q3[:, 0]
    lengths = jnp.asarray([s // 2, s][:b], jnp.int32)
    want = ref.decode_attention(q, k, v, lengths=lengths, window=window)
    got = dec.decode_attention(q, k, v, lengths=lengths, window=window,
                               block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# paged decode attention (Pallas, interpret) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "hq,hkv,d,page,window,softcap",
    [
        (4, 2, 32, 8, None, None),   # GQA, all KV heads in one block
        (4, 1, 32, 8, None, None),   # MQA
        (14, 2, 64, 16, None, None),  # qwen2-0.5b head geometry
        (4, 2, 32, 8, 12, None),     # sliding window
        (4, 2, 32, 8, None, 30.0),   # logit softcap
    ],
)
def test_paged_decode_attention_vs_ref(hq, hkv, d, page, window, softcap,
                                       dtype):
    from repro.kernels import paged_attention as pg

    n, b = 4, 3
    ks = jax.random.split(jax.random.fold_in(KEY, hq * 7 + page), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    shape = (1 + b * n, page, hkv * d)  # head h at lanes [h*d, (h+1)*d)
    kp = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], shape, jnp.float32).astype(dtype)
    # rows own disjoint, shuffled pages; page 0 is the null page
    perm = np.random.default_rng(hq + page).permutation(b * n) + 1
    bt = jnp.asarray(perm.reshape(b, n), jnp.int32)
    lengths = jnp.asarray([1, page * 2 + 3, page * n], jnp.int32)
    kw = dict(lengths=lengths, window=window, logit_softcap=softcap)
    want = ref.paged_decode_attention(q, kp, vp, bt, **kw)
    got = pg.paged_decode_attention(q, kp, vp, bt, interpret=True, **kw)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def _lane_dense_pool(cache, bt, page):
    """Lay contiguous (B, N*page, Hkv, D) caches into a (1 + B*N, page,
    Hkv*D) pool through block tables ``bt``, one head's lanes at a time:
    KV head h of position t of row b lands at lanes [h*D, (h+1)*D) of page
    bt[b, t // page], offset t % page. Page 0, the null page, holds noise."""
    cache = np.asarray(cache)
    b, _, hkv, d = cache.shape
    pool = np.random.default_rng(5).standard_normal(
        (1 + bt.size, page, hkv * d)).astype(cache.dtype)
    for r in range(b):
        for t in range(cache.shape[1]):
            for h in range(hkv):
                pool[bt[r, t // page], t % page, h * d:(h + 1) * d] = \
                    cache[r, t, h]
    return jnp.asarray(pool)


@pytest.mark.parametrize("api", ["paged_chunk_attention",
                                 "paged_decode_attention"])
def test_paged_attention_on_lane_dense_pool_vs_contiguous_oracle(api):
    """The portable paged tiers read a lane-dense pool exactly as the
    contiguous oracle reads the caches the pool was laid from."""
    b, n, page, hq, hkv, d, sq = 2, 3, 8, 4, 2, 32, 5
    _, k, v = _qkv(b, 1, n * page, hq, hkv, d, jnp.float32)
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (b, sq, hq, d))
    perm = np.random.default_rng(7).permutation(b * n) + 1
    bt = perm.reshape(b, n).astype(np.int32)
    kp, vp = _lane_dense_pool(k, bt, page), _lane_dense_pool(v, bt, page)
    lengths = jnp.asarray([page + 3, n * page], jnp.int32)
    if api == "paged_chunk_attention":
        pos = lengths[:, None] - sq + jnp.arange(sq)[None, :]
        want = ref.chunk_attention(q, k, v, positions=pos)
        got = ref.paged_chunk_attention(q, kp, vp, jnp.asarray(bt),
                                        positions=pos)
    else:
        want = ref.decode_attention(q[:, 0], k, v, lengths=lengths)
        got = ref.paged_decode_attention(q[:, 0], kp, vp, jnp.asarray(bt),
                                         lengths=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL[jnp.float32], rtol=TOL[jnp.float32])


# ---------------------------------------------------------------------------
# blocked (xla) attention vs oracle — including the MLA dv != dq case
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "sq,skv,hq,hkv,d,dv,causal,window,softcap",
    [
        (2048 + 64, 2048 + 64, 2, 1, 32, None, True, None, None),
        (2080, 4160, 3, 1, 16, None, True, None, 20.0),
        (2080, 2080, 2, 1, 32, 24, True, None, None),  # dv != dq (MLA)
        (2080, 2080, 2, 2, 32, None, True, 256, None),
    ],
)
def test_blocked_attention_vs_ref(sq, skv, hq, hkv, d, dv, causal, window,
                                  softcap):
    q, k, v = _qkv(1, sq, skv, hq, hkv, d, jnp.float32, dv=dv)
    want = ref.attention(q, k, v, causal=causal, window=window,
                         logit_softcap=softcap)
    got = ops.blocked_attention(q, k, v, causal=causal, window=window,
                                logit_softcap=softcap, block_q=256,
                                block_k=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_blocked_attention_grads_match_ref():
    q, k, v = _qkv(1, 2080, 2080, 2, 1, 16, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss(ref.attention), argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss(ops.blocked_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# ---------------------------------------------------------------------------
# chunkwise mLSTM vs quadratic oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,h,dh,chunk", [
    (64, 2, 16, 16), (128, 4, 32, 32), (100, 2, 16, 32), (256, 2, 16, 256),
])
def test_mlstm_chunkwise_vs_ref(s, h, dh, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, s * 31 + chunk), 5)
    q = jax.random.normal(ks[0], (2, s, h, dh))
    k = jax.random.normal(ks[1], (2, s, h, dh))
    v = jax.random.normal(ks[2], (2, s, h, dh))
    ig = jax.random.normal(ks[3], (2, s, h))
    fg = jax.random.normal(ks[4], (2, s, h)) + 2.0
    want = ref.mlstm(q, k, v, ig, fg)
    got = ops.mlstm_chunkwise(q, k, v, ig, fg, chunk=chunk)
    rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert rel < 5e-4


# ---------------------------------------------------------------------------
# linear recurrence oracle properties
# ---------------------------------------------------------------------------
def test_linear_recurrence_matches_loop():
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 37, 5)))
    x = jax.random.normal(ks[1], (2, 37, 5))
    h0 = jax.random.normal(ks[2], (2, 5))
    got = ref.linear_recurrence(a, x, h0=h0)
    h = h0
    for t in range(37):
        h = a[:, t] * h + x[:, t]
        np.testing.assert_allclose(np.asarray(got[:, t]), np.asarray(h),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# hook-level dispatch: binding pallas vs portable gives same numerics
# ---------------------------------------------------------------------------
def test_hook_binding_consistency():
    from repro.core import hooks

    q, k, v = _qkv(1, 128, 128, 2, 1, 32, jnp.float32)
    portable = hooks.bind(None)
    blocked = hooks.bind(None, overrides={"attention": "xla-blocked"})
    with hooks.use(portable):
        a = hooks.call("attention", q, k, v, causal=True)
    with hooks.use(blocked):
        b = hooks.call("attention", q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_hook_dispatch_through_interpret_tier():
    """On the CPU-CI profile the hand-tiled Pallas kernels serve traffic via
    the pallas-interpret tier (probed at bind time), not the portable ref."""
    from repro.core import hooks, recompile

    binding = hooks.bind(recompile.CPU_INTERPRET, probe=True)
    assert binding.providers()["decode_attention"] == "pallas-interpret"
    q3, k, v = _qkv(2, 1, 64, 4, 2, 16, jnp.float32)
    q = q3[:, 0]
    lengths = jnp.asarray([32, 64], jnp.int32)
    want = ref.decode_attention(q, k, v, lengths=lengths)
    with hooks.use(binding):
        got = hooks.call("decode_attention", q, k, v, lengths=lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rmsnorm (Pallas, interpret) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,d", [((2, 17), 64), ((3, 128), 256),
                                     ((1, 7), 100)])
def test_rmsnorm_pallas_vs_ref(shape, d, dtype):
    from repro.kernels import rmsnorm as rms

    ks = jax.random.split(jax.random.fold_in(KEY, d), 2)
    x = jax.random.normal(ks[0], (*shape, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (d,), jnp.float32) * 0.1).astype(dtype)
    want = ref.rmsnorm(x, w)
    got = rms.rmsnorm(x, w, block_rows=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# MoE grouped matmul (Pallas, interpret) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f", [(4, 64, 96, 128), (2, 100, 64, 100),
                                     (8, 16, 32, 48)])
def test_moe_gmm_pallas_vs_ref(e, c, d, f, dtype):
    from repro.kernels import moe_gmm

    ks = jax.random.split(jax.random.fold_in(KEY, e * c + f), 4)
    x = (jax.random.normal(ks[0], (e, c, d), jnp.float32) * 0.3).astype(dtype)
    wg = (jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (e, d, f), jnp.float32) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (e, f, d), jnp.float32) * 0.1).astype(dtype)
    want = ref.moe_mlp(x, wg, wu, wd)
    got = moe_gmm.moe_mlp(x, wg, wu, wd, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype] * 4, rtol=TOL[dtype] * 4)
