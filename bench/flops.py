"""The table of peaks, and the rules every family's counts follow.

A model's counts are its family's (``bench/families/<model_type>.py``):
``decode_flops``, ``prefill_flops`` and each kernel's ``<kernel>_cost``.
Model FLOPs count each multiply-add as 2 and only what the answer needs:
the weight products of each real token, attention over the keys each query
may see, and the LM head where logits are taken. Padding, masked keys and
recomputation are not counted, so they lower a utilization instead of
raising it.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """The peak row of ``device_kind``; an unknown kind is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]
