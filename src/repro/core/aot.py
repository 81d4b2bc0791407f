"""Persistent AOT specialization: serialize compiled data-plane programs.

The XaaS container story (and the follow-up "XaaS containers" source+IR
design) wants a container to carry enough specialization state to boot at
native speed on a target it has seen before — without re-tracing and
re-compiling every program in a fresh process. jax's AOT path makes that
possible: a jitted function lowered+compiled for concrete avals yields an
executable that ``jax.experimental.serialize_executable`` can turn into
bytes and load back in another process on the same platform/version.

This module is the plumbing under the engine's boot ladder:

* :func:`serialize_compiled` / :func:`deserialize_compiled` — bytes <->
  ``Compiled`` (payload + in/out pytree defs, pickled together);
* :func:`runtime_fingerprint` — the jax/jaxlib/platform triple every
  artifact key embeds (a version or backend change must invalidate);
* :func:`bundle_key` / :func:`canonical_fields` — stable content key over
  the cfg x geometry x kernel-tier x spec fields of a program bundle;
* :class:`AotProgram` — a drop-in callable replacing a bare ``jax.jit``
  function: it fingerprints call shapes, memoizes one executable per
  fingerprint (compiling on miss), and accepts pre-built executables
  *installed* from a store (the IR-boot rung);
* :class:`AotRegistry` — the per-bundle collection of AotPrograms with
  whole-bundle export/install and compile accounting;
* :func:`explain_mismatch` — human-readable reasons why a store held no
  artifact for the current bundle (stale tier, bumped jax version, ...),
  mirroring how probe-tier rejections are recorded in the manifest.
"""
from __future__ import annotations

import hashlib
import json
import logging
import pickle
import time
from typing import Any, Callable, Mapping

import jax

logger = logging.getLogger(__name__)

__all__ = [
    "AOT_AVAILABLE", "AotProgram", "AotRegistry", "bundle_key",
    "canonical_fields", "deserialize_compiled", "explain_mismatch",
    "runtime_fingerprint", "serialize_compiled",
]

try:  # jax >= 0.4.x ships this under experimental; gate rather than require
    from jax.experimental import serialize_executable as _sx
    AOT_AVAILABLE = True
except ImportError:  # pragma: no cover - every pinned env has it
    _sx = None
    AOT_AVAILABLE = False


def serialize_compiled(compiled) -> bytes:
    """A ``Compiled`` (from ``jit_fn.lower(...).compile()``) -> bytes,
    with the ids of the devices it runs on, in its device order."""
    if not AOT_AVAILABLE:
        raise RuntimeError("jax.experimental.serialize_executable unavailable")
    payload, in_tree, out_tree = _sx.serialize(compiled)
    device_ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, device_ids))


def deserialize_compiled(data: bytes):
    """bytes -> a callable ``Compiled`` loaded on the devices it was
    compiled for (raises on any malformed input, or when this process
    lacks one of those devices)."""
    if not AOT_AVAILABLE:
        raise RuntimeError("jax.experimental.serialize_executable unavailable")
    payload, in_tree, out_tree, device_ids = pickle.loads(data)
    by_id = {d.id: d for d in jax.devices()}
    return _sx.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def runtime_fingerprint() -> dict[str, str]:
    """The environment fields baked into every artifact key. A serialized
    XLA executable is only valid on the jax/jaxlib version and backend that
    produced it — any drift must miss the key and fall through to
    cold-boot. Module-level on purpose: tests monkeypatch this to simulate
    a version bump without reinstalling jax."""
    try:
        import jaxlib
        jaxlib_v = getattr(jaxlib, "__version__", jax.__version__)
    except ImportError:  # pragma: no cover
        jaxlib_v = jax.__version__
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "platform": jax.default_backend(),
    }


def canonical_fields(fields: Mapping[str, Any]) -> dict[str, str]:
    """Canonical (all-string) record of a bundle's identity fields plus the
    runtime fingerprint — what gets hashed into the key AND stored in the
    artifact's meta so a miss can be *explained* field by field."""
    rec = {str(k): repr(v) for k, v in fields.items()}
    rec.update(runtime_fingerprint())
    return rec


def bundle_key(fields: Mapping[str, Any]) -> str:
    """Content key for one program bundle: cfg x geometry x tier x spec
    fields (caller-supplied) x jax/jaxlib version x platform."""
    blob = json.dumps(canonical_fields(fields), sort_keys=True)
    return "aot-" + hashlib.sha256(blob.encode()).hexdigest()[:20]


def explain_mismatch(store, fields: Mapping[str, Any]) -> list[str]:
    """Why did ``store`` hold nothing for this bundle? Diff the current
    canonical fields against every stored artifact of the same family and
    report the differing fields — the boot ladder records these in the
    manifest the way probe rejections are recorded per tier."""
    want = canonical_fields(fields)
    reasons = []
    for key in store.keys():
        meta = store.meta(key)
        have = (meta or {}).get("fields")
        if not isinstance(have, dict):
            continue
        if have.get("family") != want.get("family"):
            continue
        # geometry/environment fields first: with >4 drifted fields the
        # truncation below must never hide "this executable was compiled
        # for a different mesh" behind cosmetic knob diffs — a wrong-mesh
        # install is the one the operator has to see
        front = ("mesh", "tiers", "jax", "jaxlib", "platform")
        keys = sorted(set(have) | set(want),
                      key=lambda k: (front.index(k) if k in front
                                     else len(front), k))
        diffs = [
            f"{k}: stored {have.get(k)} != current {want.get(k)}"
            for k in keys
            if have.get(k) != want.get(k)
        ]
        if diffs:
            reasons.append(f"stale artifact {key}: " + "; ".join(diffs[:4]))
    return reasons


class AotProgram:
    """One data-plane program behind a shape-fingerprint dispatch table.

    Wraps an (already ``jax.jit``-ed) function. Each call fingerprints the
    argument avals (shape/dtype/weak-type per leaf, pytree structure, python
    scalars by type, static args by repr) and dispatches to the compiled
    executable for that fingerprint — compiling via ``lower().compile()``
    on first sight. Executables restored from an artifact store are
    *installed* under their fingerprint and serve the same calls without
    any trace: that is the IR-boot rung.

    An installed executable that rejects the live call (aval drift the key
    failed to capture) is dropped and the call re-traces in place — the
    ladder's safety net: a stale artifact can cost a compile, never an
    error.
    """

    def __init__(self, name: str, jit_fn: Callable, *,
                 static_argnums: tuple[int, ...] = ()):
        self.name = name
        self.jit_fn = jit_fn
        self.static_argnums = frozenset(static_argnums)
        self.exes: dict[str, Any] = {}
        self.installed: set[str] = set()
        self.stats = {"compiles": 0, "installs": 0, "exe_hits": 0,
                      "fallbacks": 0}
        self.compile_s = 0.0

    # -- identity ------------------------------------------------------
    def signature(self, args) -> str:
        parts = []
        for i, a in enumerate(args):
            if i in self.static_argnums:
                parts.append(f"s{i}={a!r}")
                continue
            leaves, treedef = jax.tree_util.tree_flatten(a)
            sig = []
            for leaf in leaves:
                if isinstance(leaf, (bool, int, float, complex)) and type(
                        leaf) in (bool, int, float, complex):
                    # python scalars trace weak-typed; fingerprint by type
                    sig.append(f"py:{type(leaf).__name__}")
                else:
                    shape = tuple(getattr(leaf, "shape", ()))
                    dtype = getattr(leaf, "dtype", type(leaf).__name__)
                    weak = bool(getattr(leaf, "weak_type", False))
                    sig.append(f"{shape}:{dtype}:{int(weak)}")
            parts.append(f"a{i}={treedef}|{';'.join(sig)}")
        return hashlib.sha1("&".join(parts).encode()).hexdigest()[:16]

    # -- dispatch ------------------------------------------------------
    def _compile(self, args):
        t0 = time.perf_counter()
        exe = self.jit_fn.lower(*args).compile()
        self.compile_s += time.perf_counter() - t0
        self.stats["compiles"] += 1
        return exe

    def __call__(self, *args):
        fp = self.signature(args)
        exe = self.exes.get(fp)
        if exe is None:
            exe = self.exes[fp] = self._compile(args)
        else:
            self.stats["exe_hits"] += 1
        # executables compiled with static_argnums are called WITHOUT the
        # static args (they are baked into the trace)
        dyn = tuple(a for i, a in enumerate(args)
                    if i not in self.static_argnums)
        if fp in self.installed:
            try:
                return exe(*dyn)
            except (TypeError, ValueError) as err:
                # stale installed executable — it rejects these arguments'
                # tree, types or shardings before running: drop to the cold
                # rung for this fingerprint only. A fault while it runs is
                # not stale state and propagates.
                logger.warning("aot %s@%s: installed executable rejected the "
                               "call (%s); re-tracing", self.name, fp, err)
                self.installed.discard(fp)
                self.stats["fallbacks"] += 1
                exe = self.exes[fp] = self._compile(args)
        return exe(*dyn)

    # -- persistence ---------------------------------------------------
    def export(self) -> dict[str, bytes]:
        """``{"name@fingerprint": bytes}`` for every serializable exe."""
        out = {}
        for fp, exe in self.exes.items():
            try:
                out[f"{self.name}@{fp}"] = serialize_compiled(exe)
            except Exception as err:  # non-serializable backend/exe: skip
                logger.debug("aot export skipped %s@%s: %s",
                             self.name, fp, err)
        return out

    def install(self, fp: str, blob: bytes) -> None:
        self.exes[fp] = deserialize_compiled(blob)
        self.installed.add(fp)
        self.stats["installs"] += 1


class AotRegistry:
    """All AotPrograms of one program bundle (one ``_Programs`` /
    ``_PagedPrograms`` instance): whole-bundle export to / install from an
    artifact store, plus the compile accounting the boot manifest reports.

    Blobs installed before their program is wrapped (construction order is
    not load order) wait in ``pending`` and attach at ``wrap()`` time.
    """

    def __init__(self):
        self.programs: dict[str, AotProgram] = {}
        self._pending: dict[str, bytes] = {}

    def wrap(self, name: str, jit_fn: Callable, *,
             static_argnums: tuple[int, ...] = ()) -> AotProgram:
        prog = self.programs.get(name)
        if prog is None:
            prog = self.programs[name] = AotProgram(
                name, jit_fn, static_argnums=static_argnums)
            for key in [k for k in self._pending if
                        k.rpartition("@")[0] == name]:
                blob = self._pending.pop(key)
                try:
                    prog.install(key.rpartition("@")[2], blob)
                except Exception as err:
                    logger.warning("aot deferred install %s failed: %s",
                                   key, err)
        return prog

    # -- persistence ---------------------------------------------------
    def export(self) -> dict[str, bytes]:
        blobs = {}
        for prog in self.programs.values():
            blobs.update(prog.export())
        return blobs

    def install(self, blobs: Mapping[str, bytes]) -> tuple[int, list[str]]:
        """Install ``{"name@fp": bytes}``; returns (installed, errors).
        Unknown program names are parked for later ``wrap()`` calls."""
        installed, errors = 0, []
        for key, blob in blobs.items():
            name, _, fp = key.rpartition("@")
            prog = self.programs.get(name)
            if prog is None:
                self._pending[key] = blob
                installed += 1  # counts as installed: attaches at wrap()
                continue
            try:
                prog.install(fp, blob)
                installed += 1
            except Exception as err:
                errors.append(f"{key}: {type(err).__name__}: {err}")
        return installed, errors

    # -- accounting ----------------------------------------------------
    def compiled_count(self) -> int:
        """Executables present (compiled or installed) — nonzero means the
        bundle is warm in-process."""
        return (sum(len(p.exes) for p in self.programs.values())
                + len(self._pending))

    def compile_count(self) -> int:
        return sum(p.stats["compiles"] for p in self.programs.values())

    def counts(self) -> dict[str, int]:
        out = {"programs": len(self.programs), "executables": 0,
               "compiled": 0, "installed": 0, "exe_hits": 0, "fallbacks": 0}
        for p in self.programs.values():
            out["executables"] += len(p.exes)
            out["compiled"] += p.stats["compiles"]
            out["installed"] += p.stats["installs"]
            out["exe_hits"] += p.stats["exe_hits"]
            out["fallbacks"] += p.stats["fallbacks"]
        return out

    def compile_seconds(self) -> float:
        return sum(p.compile_s for p in self.programs.values())
