"""Deployment recompilation — the XaaS 'ship IR, specialize at the target'.

The paper's Infrastructure principle rejects binary-only portability ("compile
and test on my laptop, deploy on the largest supercomputer") in favor of
shipping a compiler intermediate representation that is *optimized at the
target architecture* (it names LLVM IR and DaCe SDFGs). JAX implements exactly
this split natively:

    trace (portable)  ->  StableHLO IR  ->  XLA compile (target-specialized)
        .lower()            portable           .compile()

This module packages that split as the XaaS deployment pipeline:

  * ``SystemProfile`` — the provider-published description of one target
    system (chip kind, peak FLOP/s, HBM bytes/bandwidth, ICI links, mesh,
    which accelerated-API providers its "system libraries" support). The
    paper's per-system tuned library set is the ``providers`` field.
  * ``DeploymentCompiler`` — lowers a traced program once (the shipped IR)
    and compiles it per target profile, caching both stages. Cold deploy =
    trace + lower + compile; warm deploy = cache hit (the paper's
    "deployable in seconds rather than minutes" claim is exercised by
    ``benchmarks/recompile_cache.py``).
  * ``CompiledArtifact`` — the deployed unit: compiled executable +
    cost/memory analysis (the single source of truth that both accounting
    and the roofline read from).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import math
import os
import pathlib
import time
from typing import Any, Callable, Mapping

import jax

from repro.kernels import compat

logger = logging.getLogger(__name__)

__all__ = [
    "SystemProfile",
    "CompiledArtifact",
    "DeploymentCompiler",
    "TPU_V5E",
    "TPU_V5E_POD",
    "TPU_V5E_2POD",
    "PORTABLE_CPU",
    "CPU_INTERPRET",
    "collective_bytes",
    "compile_cache_dir",
    "detect_profile",
    "enable_compile_cache",
]


# ---------------------------------------------------------------------------
# System profiles (the provider's published hardware + library description)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SystemProfile:
    """One target system, as advertised by its provider."""

    name: str
    chip: str  # "tpu-v5e" | "cpu" | ...
    chips: int
    peak_flops: float  # per-chip, bf16 FLOP/s
    hbm_bytes: float  # per-chip HBM capacity
    hbm_bw: float  # per-chip HBM bandwidth, bytes/s
    ici_bw: float  # per-link ICI bandwidth, bytes/s
    ici_links: int  # links per chip participating in a collective
    dcn_bw: float = 25e9  # per-host cross-pod (DCN) bandwidth, bytes/s
    mesh_shape: tuple[int, ...] = ()
    mesh_axes: tuple[str, ...] = ()
    # accelerated-API providers this system's "library set" supports
    # (consumed by hooks.bind via each impl's `supports` predicate)
    providers: tuple[str, ...] = ()
    # VMEM per chip — bounds Pallas BlockSpec working sets
    vmem_bytes: float = 128 * 2**20

    def supports(self, provider: str) -> bool:
        return provider in self.providers

    @property
    def total_peak_flops(self) -> float:
        return self.peak_flops * self.chips

    def fingerprint(self) -> str:
        return hashlib.sha1(repr(self).encode()).hexdigest()[:12]


# Per-chip peaks of one TPU v5e (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI (4 links).
_V5E = dict(
    chip="tpu-v5e",
    peak_flops=197e12,
    hbm_bytes=16 * 2**30,
    hbm_bw=819e9,
    ici_bw=50e9,
    ici_links=4,
)

TPU_V5E = SystemProfile(
    name="tpu-v5e-1",
    chips=1,
    mesh_shape=(1,),
    mesh_axes=("data",),
    providers=("pallas-tpu",),
    **_V5E,
)

TPU_V5E_POD = SystemProfile(
    name="tpu-v5e-pod-256",
    chips=256,
    mesh_shape=(16, 16),
    mesh_axes=("data", "model"),
    providers=("pallas-tpu",),
    **_V5E,
)

TPU_V5E_2POD = SystemProfile(
    name="tpu-v5e-2pod-512",
    chips=512,
    mesh_shape=(2, 16, 16),
    mesh_axes=("pod", "data", "model"),
    providers=("pallas-tpu",),
    **_V5E,
)

# The portability floor: any XLA-capable host, no system libraries.
PORTABLE_CPU = SystemProfile(
    name="portable-cpu",
    chip="cpu",
    chips=1,
    peak_flops=1e11,
    hbm_bytes=8 * 2**30,
    hbm_bw=50e9,
    ici_bw=1e9,
    ici_links=1,
    mesh_shape=(1,),
    mesh_axes=("data",),
    providers=(),
)

# A CPU host whose "library set" includes the Pallas interpreter and the
# blocked pure-XLA tier: what CPU CI deploys, so the hand-tiled kernels are
# exercised (through the pallas-interpret tier) rather than skipped.
CPU_INTERPRET = SystemProfile(
    name="cpu-pallas-interpret",
    chip="cpu",
    chips=1,
    peak_flops=1e11,
    hbm_bytes=8 * 2**30,
    hbm_bw=50e9,
    ici_bw=1e9,
    ici_links=1,
    mesh_shape=(1,),
    mesh_axes=("data",),
    providers=("pallas-interpret", "xla-blocked"),
)


@functools.lru_cache(maxsize=None)
def host_mesh_profile(
    mesh_shape: tuple[int, ...],
    mesh_axes: tuple[str, ...] | None = None,
    *,
    hbm_bytes: int = 8 * 2**30,
) -> SystemProfile:
    """A multi-chip host-platform (CPU) profile: N forced host devices
    standing in for an N-chip accelerator slice, so sharded serving replicas
    can be leased, deployed, metered, and parity-checked without TPUs
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set
    before jax initializes for ``build_mesh`` to find the devices).

    Leases acquired against this profile request ``chips = prod(mesh_shape)``
    — the replica-width unit the fleet's width-vs-count policy trades in.
    Per-chip roofline terms (peak_flops, hbm_bytes, hbm_bw) match
    PORTABLE_CPU so modeled step times stay comparable across widths. The
    lru_cache keeps the instance (and thus ``fingerprint()`` identity used
    by warm-deployment caches) stable for a given geometry."""
    if mesh_axes is None:
        mesh_axes = ("data", "model")[-len(mesh_shape):] if len(
            mesh_shape) <= 2 else ("pod", "data", "model")[-len(mesh_shape):]
    if len(mesh_axes) != len(mesh_shape):
        raise ValueError(
            f"mesh_axes {mesh_axes} does not match mesh_shape {mesh_shape}")
    chips = 1
    for d in mesh_shape:
        chips *= int(d)
    geom = "x".join(str(int(d)) for d in mesh_shape)
    return SystemProfile(
        name=f"cpu-mesh-{geom}",
        chip="cpu",
        chips=chips,
        peak_flops=1e11,
        hbm_bytes=hbm_bytes,
        hbm_bw=50e9,
        ici_bw=1e9,
        ici_links=1,
        mesh_shape=tuple(int(d) for d in mesh_shape),
        mesh_axes=tuple(mesh_axes),
        providers=(),
    )


# The one table from what the runtime reports (``device.device_kind``) to
# the single-chip profile of that device. A kind missing here is an error,
# never a default: peaks assumed for an unknown chip would be wrong.
_PROFILE_BY_KIND: dict[str, SystemProfile] = {
    "TPU v5 lite": TPU_V5E,
    "cpu": PORTABLE_CPU,
}


@functools.lru_cache(maxsize=None)
def _chip_mesh_profile(base: SystemProfile,
                       mesh_shape: tuple[int, ...]) -> SystemProfile:
    # no Pallas tier: JAX refuses to partition a Mosaic kernel inside a
    # program that spans several devices (it would need a shard_map around
    # each kernel), so a sharded replica serves through the portable tiers
    geom = "x".join(str(d) for d in mesh_shape)
    return dataclasses.replace(
        base, name=f"{base.chip}-mesh-{geom}", chips=math.prod(mesh_shape),
        mesh_shape=mesh_shape,
        mesh_axes=("data", "model")[-len(mesh_shape):], providers=())


def detect_profile(mesh_shape: tuple[int, ...] | None = None, *,
                   devices=None) -> SystemProfile:
    """The profile of the devices this process runs on (``jax.devices()``
    unless ``devices`` is given), for a replica spanning ``mesh_shape``
    (one chip when None). Raises ``ValueError`` for a device kind with no
    entry in the table; a CPU host maps to ``PORTABLE_CPU`` or, for a
    wider mesh, to ``host_mesh_profile``."""
    devices = jax.devices() if devices is None else devices
    kind = devices[0].device_kind
    base = _PROFILE_BY_KIND.get(kind)
    if base is None:
        raise ValueError(
            f"no SystemProfile for device kind {kind!r} "
            f"(known: {sorted(_PROFILE_BY_KIND)})")
    shape = tuple(int(d) for d in mesh_shape) if mesh_shape else (1,)
    if math.prod(shape) == 1:
        return base
    if base.chip == "cpu":
        return host_mesh_profile(shape)
    return _chip_mesh_profile(base, shape)


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------
def compile_cache_dir() -> pathlib.Path | None:
    """Where entry points keep JAX's persistent compilation cache: None
    when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads that itself),
    else ``.jax_cache/`` at the root of this checkout. The path is part of
    every cache key, so it is fixed: never a temp name, PID or time."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn the persistent compilation cache on at ``compile_cache_dir()``.
    Entry points call this from ``main``; importing a module never does."""
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", str(path))


# ---------------------------------------------------------------------------
# HLO collective parsing (roofline collective term; not in cost_analysis)
# ---------------------------------------------------------------------------
_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "e4m3": 1, "e5m2": 1,
}


def _shape_bytes(shape_str: str) -> int:
    """'bf16[256,4096]' -> byte count. Tuples handled by caller."""
    shape_str = shape_str.strip()
    if "[" not in shape_str:
        return 0
    dt, dims = shape_str.split("[", 1)
    dims = dims.split("]", 1)[0]
    n = 1
    if dims:
        for d in dims.split(","):
            d = d.strip().lstrip("<=")  # dynamic dims "<=128"
            if d:
                n *= int(d)
    return n * _DTYPE_BYTES.get(dt.strip(), 4)


def _result_shapes(line: str) -> list[str]:
    """Shapes on the LHS of an HLO instruction (tuple results flattened)."""
    lhs = line.split("=", 1)[0]
    # e.g. "  %all-reduce.1 = (bf16[128,8]{1,0}, bf16[64]{0}) all-reduce(..."
    # or "  %ag = bf16[512,1024]{1,0} all-gather(..."
    rhs = line.split("=", 1)[1] if "=" in line else ""
    out, depth, cur = [], 0, ""
    # take the type prefix of the RHS up to the op name
    for tok in rhs.strip().split(" "):
        if any(tok.startswith(op) for op in _COLLECTIVE_OPS):
            break
        cur += tok
    cur = cur.strip()
    if cur.startswith("("):
        cur = cur[1:].rsplit(")", 1)[0]
        for part in cur.split("),"):
            part = part.split("{")[0]
            if "[" in part:
                out.append(part)
        # simpler: split on "]," boundaries
        out = []
        buf = ""
        for ch in cur:
            buf += ch
            if ch == "]":
                out.append(buf.strip().lstrip(","))
                buf = ""
    elif "[" in cur:
        out.append(cur.split("{")[0])
    return out


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-operand bytes of every collective op in an HLO module.

    Returns {op_kind: bytes} + {"total": sum}. Uses the *result* shapes
    (for all-gather that is the gathered size, for reduce-scatter the
    scattered size — a consistent, conservative proxy for wire bytes).
    """
    out = {k: 0 for k in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls or "=" not in ls:
            continue
        body = ls.split("=", 1)[1]
        kind = None
        for op in _COLLECTIVE_OPS:
            # match op name at an instruction position: " all-reduce(" etc.
            if f" {op}(" in body or body.strip().startswith(f"{op}("):
                kind = op
                break
        # exclude -start/-done split pairs double count: count only -start
        # (async) or plain ops; '-done' carries the same shape.
        if kind is None:
            for op in _COLLECTIVE_OPS:
                if f" {op}-start(" in body:
                    kind = op
                    break
        if kind is None or f" {kind}-done(" in body:
            continue
        for shp in _result_shapes(ls):
            out[kind] += _shape_bytes(shp)
    out["total"] = sum(out[k] for k in _COLLECTIVE_OPS)
    return out


# ---------------------------------------------------------------------------
# Deployment pipeline
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CompiledArtifact:
    """A deployed XaaS program: one compiled executable + its analyses."""

    key: str
    profile: SystemProfile
    lowered: Any  # jax.stages.Lowered (None when restored from a store)
    compiled: Any  # jax.stages.Compiled
    lower_s: float
    compile_s: float
    cache_hit: bool
    # how this executable came to exist in this process: "cold"
    # (traced+compiled here), "warm" (in-process exe-cache hit), or "ir"
    # (deserialized from a persistent ArtifactStore)
    boot: str = "cold"

    _cost: dict | None = None
    _memory: Any = None
    _collectives: dict[str, int] | None = None

    def cost_analysis(self) -> dict:
        if self._cost is None:
            self._cost = compat.xla_cost_analysis(self.compiled)
        return self._cost

    def memory_analysis(self):
        if self._memory is None:
            self._memory = self.compiled.memory_analysis()
        return self._memory

    def collectives(self) -> dict[str, int]:
        if self._collectives is None:
            self._collectives = collective_bytes(self.compiled.as_text())
        return self._collectives

    @property
    def flops(self) -> float:
        return float(self.cost_analysis().get("flops", 0.0))

    @property
    def hbm_bytes(self) -> float:
        c = self.cost_analysis()
        return float(c.get("bytes accessed", 0.0))

    def __call__(self, *args, **kwargs):
        return self.compiled(*args, **kwargs)


class DeploymentCompiler:
    """Two-stage cache: traced IR per program, executable per (IR, target).

    ``deploy(fn, name, profile, in_shardings=..., args=...)``:
      stage 1 (portable): jit(fn).lower(*args) — cached on (name, arg
          shapes/dtypes). This is the 'container image' the paper ships.
      stage 2 (target): lowered.compile() — cached additionally on the
          profile fingerprint + sharding. This is deployment recompilation.
    """

    def __init__(self):
        self._ir_cache: dict[str, tuple[Any, float]] = {}
        self._exe_cache: dict[str, CompiledArtifact] = {}
        self.stats = {"ir_hits": 0, "ir_misses": 0, "exe_hits": 0, "exe_misses": 0}

    @staticmethod
    def _arg_key(args, kwargs) -> str:
        leaves = jax.tree.leaves((args, kwargs))
        parts = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            sh = getattr(leaf, "sharding", None)
            parts.append(f"{shape}:{dtype}:{sh}")
        return hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]

    def lower(self, fn: Callable, name: str, args=(), kwargs=None,
              jit_kwargs: Mapping[str, Any] | None = None):
        """Stage 1: trace to portable IR (cached)."""
        kwargs = kwargs or {}
        key = f"{name}:{self._arg_key(args, kwargs)}:{id(fn)}"
        if key in self._ir_cache:
            self.stats["ir_hits"] += 1
            return key, *self._ir_cache[key]
        t0 = time.perf_counter()
        lowered = jax.jit(fn, **(jit_kwargs or {})).lower(*args, **kwargs)
        dt = time.perf_counter() - t0
        self._ir_cache[key] = (lowered, dt)
        self.stats["ir_misses"] += 1
        return key, lowered, dt

    def _store_key(self, name: str, args, kwargs, profile: SystemProfile,
                   extra: Mapping[str, Any] | None = None) -> str:
        """Process-stable artifact key for one deployed entrypoint. Unlike
        the in-process IR key it must NOT include id(fn); aot.bundle_key
        folds in jax/jaxlib version + platform so environment drift misses
        cleanly. ``extra`` carries caller identity fields — the container
        deploy path passes the probed kernel-tier fingerprint, so a tier
        change invalidates stored entrypoints exactly like engine bundles."""
        from repro.core import aot
        fields = {
            "family": f"entrypoint:{name}",
            "args": self._arg_key(args, kwargs),
            "profile": profile.fingerprint(),
        }
        if extra:
            fields.update(extra)
        return aot.bundle_key(fields)

    def _ir_restore(self, skey: str, name: str, profile: SystemProfile,
                    store) -> CompiledArtifact | None:
        """IR-boot rung for a deployed entrypoint: deserialize a stored
        executable instead of lower+compile. The stored meta carries the
        cost/collective analyses so the metering and dry-run paths keep
        working without the Lowered stage."""
        from repro.core import aot
        got = store.get(skey)
        if got is None:
            return None
        blobs, meta = got
        try:
            compiled = aot.deserialize_compiled(blobs["exe"])
        except Exception:
            return None
        return CompiledArtifact(
            key=f"{skey}@{profile.fingerprint()}",
            profile=profile,
            lowered=None,
            compiled=compiled,
            lower_s=0.0,
            compile_s=0.0,
            cache_hit=False,
            boot="ir",
            _cost=meta.get("cost"),
            _collectives=meta.get("collectives"),
        )

    def _persist(self, skey: str, name: str, art: CompiledArtifact,
                 store) -> None:
        from repro.core import aot
        try:
            blob = aot.serialize_compiled(art.compiled)
            meta = {
                "name": name,
                "cost": {k: float(v) for k, v in art.cost_analysis().items()
                         if isinstance(v, (int, float))},
                "collectives": art.collectives(),
            }
            store.put(skey, {"exe": blob}, meta=meta)
        except Exception as err:  # non-serializable exe: stay cold-bootable
            self.stats["persist_failures"] = (
                self.stats.get("persist_failures", 0) + 1)
            logger.debug("artifact persist skipped for %s: %s", name, err)

    def deploy(
        self,
        fn: Callable,
        name: str,
        profile: SystemProfile,
        *,
        args=(),
        kwargs=None,
        jit_kwargs: Mapping[str, Any] | None = None,
        store=None,
        store_extra: Mapping[str, Any] | None = None,
    ) -> CompiledArtifact:
        """Full deployment: lower (or reuse IR) + compile for `profile`.
        With ``store`` (an ArtifactStore), the boot ladder applies: a
        matching persisted executable deserializes instead of compiling
        (boot="ir"), and a cold compile persists for the next process."""
        skey = None
        if store is not None:
            skey = self._store_key(name, args, kwargs, profile, store_extra)
            cached = self._exe_cache.get(skey)
            if cached is not None:
                self.stats["exe_hits"] += 1
                return dataclasses.replace(cached, cache_hit=True,
                                           boot="warm")
            art = self._ir_restore(skey, name, profile, store)
            if art is not None:
                self._exe_cache[skey] = art
                self.stats["ir_boots"] = self.stats.get("ir_boots", 0) + 1
                return art
        ir_key, lowered, lower_s = self.lower(fn, name, args, kwargs, jit_kwargs)
        exe_key = f"{ir_key}@{profile.fingerprint()}"
        if exe_key in self._exe_cache:
            self.stats["exe_hits"] += 1
            art = self._exe_cache[exe_key]
            return dataclasses.replace(art, cache_hit=True, boot="warm")
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        art = CompiledArtifact(
            key=exe_key,
            profile=profile,
            lowered=lowered,
            compiled=compiled,
            lower_s=lower_s,
            compile_s=compile_s,
            cache_hit=False,
        )
        self._exe_cache[exe_key] = art
        self.stats["exe_misses"] += 1
        if skey is not None:
            self._exe_cache[skey] = art
            self._persist(skey, name, art, store)
        return art


# Module-level default compiler (one per process, like a local registry).
DEFAULT_COMPILER = DeploymentCompiler()
