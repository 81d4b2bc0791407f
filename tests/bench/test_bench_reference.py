"""The plain reference against the program's logits at the smoke size,
through the paged chunked prefill and the paged decode step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bm
from bench import weights
from bench.families import qwen2
from benchutil import TINY_MODEL


def _program_logits(model, seed, toks, steps):
    """Logits of a paged prefill in chunks of 16 and ``steps`` greedy
    decode steps, through the program's own model functions."""
    from repro.models import transformer

    conf = {"model": model}
    cfg = qwen2.arch_config("tiny", conf)
    params = bm.make_params(qwen2, seed, conf, cfg)
    dt = jnp.dtype(cfg.activ_dtype)
    ps, nb = 16, 8
    states = transformer.init_paged_states(cfg, 1 + nb, ps, dt)
    bt = jnp.arange(1, 1 + nb, dtype=jnp.int32)[None]
    pos = 0
    while pos < len(toks):
        chunk = toks[pos: pos + 16]
        t = np.zeros((1, 16), np.int32)
        t[0, :len(chunk)] = chunk
        lg, states, _ = transformer.prefill_chunk(
            params, cfg, jnp.asarray(t), states, jnp.array([pos]),
            jnp.array([pos + len(chunk)]), block_tables=bt, page_size=ps)
        pos += len(chunk)
    out, served = [np.asarray(lg[0], np.float32)], [int(jnp.argmax(lg[0]))]
    for i in range(steps):
        lg, states = transformer.decode_step(
            params, cfg, jnp.array([served[-1]]), states,
            jnp.array([len(toks) + i + 1]), block_tables=bt, page_size=ps)
        out.append(np.asarray(lg[0], np.float32))
        served.append(int(jnp.argmax(lg[0])))
    return np.stack(out), served


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_the_program_in_f32(tied):
    model = dict(TINY_MODEL, torch_dtype="float32", tie_word_embeddings=tied)
    toks = np.random.default_rng(0).integers(0, 256, 37).astype(np.int32)
    got, served = _program_logits(model, 11, toks, steps=6)
    seq = np.concatenate([toks, served[:-1]]).astype(np.int32)
    want = np.arange(len(toks) - 1, len(seq))
    ref = np.asarray(qwen2.Reference({"model": model}, 11)
                     .logits([seq], [want], 512)[0])
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale


def test_bf16_program_is_close_and_the_fp8_control_is_not():
    toks = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    got, served = _program_logits(TINY_MODEL, 3, toks, steps=8)
    seq = np.concatenate([toks, served[:-1]]).astype(np.int32)
    want = np.arange(len(toks) - 1, len(seq))
    conf = {"model": TINY_MODEL}
    ref = np.asarray(qwen2.Reference(conf, 3).logits([seq], [want], 512)[0])
    low = np.asarray(qwen2.Reference(conf, 3, fp8=True)
                     .logits([seq], [want], 512)[0])
    err_prog = np.abs(got - ref).max()
    err_low = np.abs(low - ref).max()
    assert err_low > 3 * err_prog


def test_one_layer_made_alone_equals_the_stacked_tree():
    model = dict(TINY_MODEL, num_hidden_layers=3)
    conf = {"model": model}
    cfg = qwen2.arch_config("tiny", conf)
    params = bm.make_params(qwen2, 2**31 + 9, conf, cfg)
    alone = qwen2.layer_tensors(weights.seed_key(2**31 + 9), model, 2,
                                jnp.bfloat16)
    stacked = params["scan"][0]
    assert np.array_equal(np.asarray(alone["down_w"]),
                          np.asarray(stacked["ffn"]["w_down"]["w"][2]))
    assert np.array_equal(np.asarray(alone["k_b"]),
                          np.asarray(stacked["mixer"]["wk"]["b"][2]))


def test_a_changed_program_layout_is_refused(monkeypatch):
    from repro.models import transformer

    conf = {"model": TINY_MODEL}
    cfg = qwen2.arch_config("tiny", conf)
    real = transformer.init_model

    def renamed(key, cfg):
        p = real(key, cfg)
        p["final_norm"] = {"gain": p["final_norm"]["w"]}
        return p

    monkeypatch.setattr(transformer, "init_model", renamed)
    with pytest.raises(RuntimeError, match="layout differs"):
        bm.make_params(qwen2, 0, conf, cfg)
    assert jax.devices()
