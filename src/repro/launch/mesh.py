"""Production mesh construction (assignment-fixed shapes).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS for 512 stand-in host devices
before any jax import, and only then calls this.
"""
from __future__ import annotations

import jax

from repro.distributed import sharding as shd

__all__ = ["make_production_mesh", "mesh_axis_sizes", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (data=16, model=16) = 256 chips of TPU v5e.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; `pod` crosses DCN."""
    import math

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) == n:
        return shd.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run via launch/dryrun.py (it forces 512 stand-in host devices)")
    # more devices than the mesh needs (e.g. single-pod mesh in a 512-device
    # dry-run process): take a prefix slice
    return shd.make_mesh(shape, axes, devices[:n])


def mesh_axis_sizes(mesh: jax.sharding.Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh: jax.sharding.Mesh):
    """The mesh axes the global batch shards over (pure DP across pods)."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"
