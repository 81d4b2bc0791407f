"""Operation and byte counts against hand arithmetic, and the peaks
table."""
import pytest

from bench import flops
from bench.families import qwen2

# d 8, f 16, 2 query heads of 4, 1 KV head, 3 layers, vocab 10
M = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
     "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 10,
     "tie_word_embeddings": True}
CONF = {"model": M}


def test_layer_and_head_params():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    assert qwen2.layer_params(M) == 64 + 32 + 32 + 64 + 3 * 128
    assert qwen2.head_params(M) == 80


def test_decode_flops():
    per = 2 * (576 * 3 + 80)
    # 4 * Hq * D per key per layer = 4 * 2 * 4 * 3 = 96 per key
    assert qwen2.decode_flops(CONF, [5, 7]) == 2 * per + 96 * 12


def test_prefill_flops_is_causal_and_counts_only_real_positions():
    # positions 2..4 attend 3 + 4 + 5 keys; one head row per span
    f = qwen2.prefill_flops(CONF, [(2, 5)])
    assert f == 2 * 576 * 3 * 3 + 96 * (3 + 4 + 5) + 2 * 80


def test_paged_decode_attention_cost():
    f, b = qwen2.paged_decode_attention_cost(CONF, keys=100)
    assert f == 4 * 2 * 4 * 100 * 3
    # K and V, 1 head of 4, 2 bytes, per key per layer
    assert b == 2 * 1 * 4 * 2 * 100 * 3


def test_peaks_for_the_v5e_and_unknown_kinds_raise():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
