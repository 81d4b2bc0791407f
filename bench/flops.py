"""Operations and bytes the model's mathematics needs, from its shapes.

Model FLOPs count each multiply-add as 2 and only what the answer needs:
the weight products of each real token, attention over the keys each query
may see, and the LM head where logits are taken. Padding, masked keys and
recomputation are not counted, so they lower a utilization instead of
raising it.
"""
from __future__ import annotations

import json
import pathlib

from bench import weights

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """The peak row of ``device_kind``; an unknown kind is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def layer_params(model: dict) -> int:
    """Weights one token multiplies in one layer (biases and gains left
    out: they add, not multiply)."""
    m = weights.dims(model)
    q, kv = m["hq"] * m["hd"], m["hkv"] * m["hd"]
    return m["d"] * (2 * q + 2 * kv) + 3 * m["d"] * m["f"]


def head_params(model: dict) -> int:
    m = weights.dims(model)
    return m["d"] * m["v"]


def attn_flops(model: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, all layers and heads."""
    m = weights.dims(model)
    return 4 * m["hq"] * m["hd"] * keys * m["layers"]


def decode_flops(model: dict, ctx: list[int]) -> int:
    """One decode token per entry of ``ctx``, each attending ``ctx[i]`` keys
    (its own included), with the LM head."""
    per = 2 * (layer_params(model) * weights.dims(model)["layers"]
               + head_params(model))
    return per * len(ctx) + sum(attn_flops(model, c) for c in ctx)


def prefill_flops(model: dict, spans: list[tuple[int, int]]) -> int:
    """Prompt positions [a, b) of each span, causal, with the LM head once
    per span (a chunk's logits are taken at its last position)."""
    m = weights.dims(model)
    per = 2 * layer_params(model) * m["layers"]
    n = sum(b - a for a, b in spans)
    # position p attends p + 1 keys: sum over [a, b) of (p + 1)
    keys = sum((b * (b + 1) - a * (a + 1)) // 2 for a, b in spans)
    return per * n + attn_flops(model, 1) * keys \
        + 2 * head_params(model) * len(spans)


def paged_decode_attention_cost(model: dict, keys: int,
                                kv_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of the decode attention kernel over ``keys`` cached
    positions summed over rows, all layers: 4·Hq·D FLOPs per key, and its K
    and V rows read once per layer."""
    m = weights.dims(model)
    flops = 4 * m["hq"] * m["hd"] * keys * m["layers"]
    nbytes = 2 * m["hkv"] * m["hd"] * kv_bytes * keys * m["layers"]
    return flops, nbytes
