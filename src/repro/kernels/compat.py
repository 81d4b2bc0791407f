"""Small adapters between the kernels, the cost model and the installed JAX.

Kernels build their Pallas TPU parameters with ``pltpu.CompilerParams`` and
``pltpu.PrefetchScalarGridSpec`` directly: the code targets the installed
JAX only. What stays here is used in more than one place: the VMEM scratch
allocation every kernel declares, and the normalization of
``Compiled.cost_analysis()`` that the deployment compiler and the HLO cost
model both read.

Nothing in here may assume a TPU is attached: all helpers must resolve at
import time on any XLA host.
"""
from __future__ import annotations

from typing import Any, Mapping

from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "normalize_cost_analysis",
    "xla_cost_analysis",
    "vmem",
]


def vmem(shape: tuple[int, ...], dtype) -> Any:
    """A VMEM scratch allocation."""
    return pltpu.VMEM(shape, dtype)


def normalize_cost_analysis(raw: Any) -> dict:
    """``jax.stages.Compiled.cost_analysis()`` output as one dict: the
    installed JAX returns a dict, or ``None`` on a backend without a cost
    model."""
    if raw is None:
        return {}
    if isinstance(raw, Mapping):
        return dict(raw)
    raise TypeError(
        f"unrecognized cost_analysis() format: {type(raw).__name__}")


def xla_cost_analysis(compiled: Any) -> dict:
    """``compiled.cost_analysis()`` normalized; ``{}`` if unsupported."""
    try:
        raw = compiled.cost_analysis()
    except NotImplementedError:
        return {}
    return normalize_cost_analysis(raw)
