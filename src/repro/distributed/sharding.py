"""Logical-axis sharding: t5x-style rules mapping logical names to mesh axes.

Model code annotates activations with *logical* axes via ``constraint(x,
"batch", "seq", ...)`` and never mentions mesh axes; a deployment installs a
rule set (``with use_rules(RULES_2D):``) that resolves logical names to mesh
axes. Outside a rules scope the constraints are no-ops, so the same model code
runs unsharded on one CPU device — the XaaS portability floor.

Parameter sharding is path-based: ``param_pspec_tree`` walks a param pytree
and matches parameter path suffixes against PARAM_RULES (consistent layer
naming in models/ makes this total).
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# logical axis name -> mesh axis (str | tuple | None)
Rules = dict[str, object]

# Single-pod production mesh (16, 16) = 256 chips.
RULES_2D: Rules = {
    "batch": "data",
    "seq": None,
    "kv_seq": None,  # flipped to "model" for sequence-sharded decode recipes
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "expert_cap": None,
    "expert_group": "data",
    # MoE dispatch/combine width (the D dim of permutation-gather buffers):
    # sharded over model so dispatch memory is O(tokens*k*D/TP) per chip
    "moe_d": "model",
    "vocab": "model",
    "embed": None,
    # parameter hidden dims (PARAM_RULES only): "data" under FSDP recipes —
    # distinct from activation "embed" so batch/data never collide
    "p_embed": None,
    # serving-state batch dim (KV caches / recurrent states) — usually the
    # same as "batch", but decode recipes may replicate activations while
    # keeping the cache batch-sharded
    "state_batch": "data",
    "lru": "model",
    "stack": None,
}

# Multi-pod mesh (pod, data, model): pure DP across pods; the expert-major
# all-to-all layout (E, B*cap, D) keeps tokens pod-local via expert_cap.
RULES_3D: Rules = dict(RULES_2D, batch=("pod", "data"),
                       state_batch=("pod", "data"),
                       expert_group=("pod", "data"), expert_cap="pod")


class _State(threading.local):
    def __init__(self):
        self.rules: Rules | None = None
        self.mesh: jax.sharding.Mesh | None = None


_STATE = _State()


@contextlib.contextmanager
def use_rules(rules: Rules | None, mesh: jax.sharding.Mesh | None = None):
    prev = (_STATE.rules, _STATE.mesh)
    _STATE.rules = rules
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.rules, _STATE.mesh = prev


def current_rules() -> Rules | None:
    return _STATE.rules


def current_mesh() -> jax.sharding.Mesh | None:
    return _STATE.mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """A mesh whose axes are all ``Auto``: ``constraint`` lowers through
    ``with_sharding_constraint``, which refuses the ``Explicit`` axes that
    ``jax.make_mesh`` builds by default. ``devices`` picks the grid's
    devices (in order); by default ``jax.make_mesh`` chooses them."""
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    grid = np.asarray(devices, dtype=object).reshape(tuple(shape))
    return jax.sharding.Mesh(grid, tuple(axes), axis_types=types)


def mesh_geometry(
    mesh: jax.sharding.Mesh | None,
) -> tuple[tuple[int, ...], tuple[str, ...]] | None:
    """(device-grid shape, axis names) of a mesh — the geometry fingerprint
    that keys serving program bundles and persisted AOT artifacts. None is
    the unsharded single-device floor, so adding a mesh (or changing its
    shape) re-keys every compiled program while the floor keys stay put."""
    if mesh is None:
        return None
    return (tuple(int(s) for s in mesh.devices.shape),
            tuple(str(a) for a in mesh.axis_names))


def rule_summary(rules: Rules | None) -> dict[str, str | None]:
    """JSON-able view of a rule set (tuples joined with '+') for manifests
    and the launch CLI — logical axis -> mesh axis, sorted by logical name."""
    if rules is None:
        return {}
    out: dict[str, str | None] = {}
    for name in sorted(rules):
        entry = rules[name]
        if isinstance(entry, tuple):
            out[name] = "+".join(entry)
        else:
            out[name] = entry
    return out


def _axis_size(mesh, entry) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def resolve(*logical: str | None) -> P:
    rules = _STATE.rules or {}
    axes = []
    for name in logical:
        if name is None:
            axes.append(None)
        else:
            axes.append(rules.get(name))
    return P(*axes)


def guarded_spec(shape: tuple[int, ...], logical: tuple[str | None, ...]) -> P:
    """Resolve logical axes -> PartitionSpec, dropping (replicating) any axis
    whose dimension is not divisible by its mesh extent, and any mesh axis
    already claimed by an earlier dim (rule sets may map two logical axes to
    one mesh axis — e.g. EP over (data, model) plus FSDP p_embed->data; the
    first/leading use wins). This is the portability guard: archs whose head
    counts etc. don't divide the fixed production mesh still compile — the
    waste shows up honestly in the roofline terms instead of as a sharding
    error."""
    spec = resolve(*logical)
    mesh = _STATE.mesh
    if mesh is None:
        return spec
    out = []
    used: set[str] = set()
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        names = tuple(entry) if isinstance(entry, tuple) else (entry,)
        # drop axes already claimed AND axes the mesh doesn't have (a rule
        # set naming "model" must still deploy on a data-only mesh)
        names = tuple(n for n in names if n not in used and n in mesh.shape)
        # tuple entries degrade by dropping trailing axes until divisible
        # (e.g. batch=256 on ("pod","data","model")=512 -> ("pod","data")=32)
        while names and dim % _axis_size(mesh, names) != 0:
            names = names[:-1]
        if not names:
            out.append(None)
        else:
            out.append(names if len(names) > 1 else names[0])
            used.update(names)
    return P(*out)


def constraint(x: jax.Array, *logical: str | None) -> jax.Array:
    """Annotate activation sharding by logical axes; no-op outside rules."""
    if _STATE.rules is None:
        return x
    spec = guarded_spec(x.shape, logical)
    if all(a is None for a in spec):
        return x
    if _STATE.mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(_STATE.mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter sharding by path
# ---------------------------------------------------------------------------
# (regex on ".../"-joined param path, logical axes for the trailing dims).
# Later rules win; first two dims of stacked-layer params get the extra
# leading "stack" axis automatically (detected by ndim mismatch).
PARAM_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    (r"embed/w$", ("vocab", "p_embed")),
    (r"codebook_embed/w$", (None, "vocab", "p_embed")),
    (r"lm_head/w$", ("p_embed", "vocab")),
    (r"codebook_head/w$", (None, "p_embed", "vocab")),
    (r"patch_proj/w$", (None, "p_embed")),
    (r"w[qkv]/w$", ("p_embed", "heads")),
    (r"w[qkv]/b$", ("heads",)),
    (r"wo/w$", ("heads", "p_embed")),
    (r"wo/b$", (None,)),
    (r"(w_gate|w_up)/w$", ("p_embed", "ff")),
    (r"w_down/w$", ("ff", "p_embed")),
    # MoE expert weights: (E, D, F) / (E, F, D)
    (r"experts/w_gate$", ("experts", "p_embed", "ff")),
    (r"experts/w_up$", ("experts", "p_embed", "ff")),
    (r"experts/w_down$", ("experts", "ff", "p_embed")),
    (r"router/w$", ("p_embed", None)),
    (r"router/bias$", (None,)),
    # MLA
    (r"w_dq/w$", ("p_embed", None)),
    (r"w_uq/w$", (None, "heads")),
    (r"w_dkv/w$", ("p_embed", None)),
    (r"w_uk/w$", (None, "heads")),
    (r"w_uv/w$", (None, "heads")),
    # RG-LRU / recurrent blocks
    (r"(lru_in|lru_gate)/w$", ("p_embed", "lru")),
    (r"lru_out/w$", ("lru", "p_embed")),
    (r"(w_a|w_x)/w$", ("lru", "lru")),  # diagonal-ish gates stay lru-sharded
    (r"rglru/(lam|b_a|b_x)$", ("lru",)),
    (r"conv/(w|b)$", (None, "lru")),
    # xLSTM
    (r"(up_proj|up_gate)/w$", ("p_embed", "ff")),
    (r"down_proj/w$", ("ff", "p_embed")),
    (r"(wq_in|wk_in|wv_in)/w$", ("lru", None, None)),  # block-diag (nb,bs,bs)
    (r"(wi_in|wf_in|wo_in)/w$", ("ff", "heads")),
    (r"(wi_in|wf_in)/b$", ("heads",)),
    (r"slstm/(wz|wi|wf|wo)/w$", ("p_embed", "heads")),
    (r"slstm/(rz|ri|rf|ro)$", ("heads", None, None)),
    (r"slstm/(bz|bi|bf|bo)$", ("heads",)),
    # norms / scalars: replicated
    (r".*", (None,)),
]

_COMPILED = [(re.compile(pat), spec) for pat, spec in PARAM_RULES]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def logical_param_axes(params) -> object:
    """Pytree of logical-axis tuples parallel to `params`."""

    def annotate(path, leaf):
        s = _path_str(path)
        for pat, spec in _COMPILED:
            if pat.search(s):
                if len(spec) < leaf.ndim:
                    spec2 = ("stack",) * (leaf.ndim - len(spec)) + tuple(spec)
                elif len(spec) > leaf.ndim:
                    spec2 = tuple(spec[-leaf.ndim:])
                else:
                    spec2 = tuple(spec)
                return spec2
        raise AssertionError(f"no param rule matched {s}")

    return jax.tree_util.tree_map_with_path(annotate, params)


def param_pspecs(params) -> object:
    """Pytree of PartitionSpec for `params` under the current rules
    (divisibility-guarded when a mesh is installed)."""
    axes = logical_param_axes(params)
    is_axes = lambda t: (
        isinstance(t, tuple) and len(t) > 0
        and all(isinstance(a, str) or a is None for a in t))
    return jax.tree.map(
        lambda a, p: guarded_spec(p.shape, a), axes, params, is_leaf=is_axes)


def param_shardings(params, mesh) -> object:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(params))


# ---------------------------------------------------------------------------
# Serving-state sharding by path (KV caches, recurrent states)
# ---------------------------------------------------------------------------
# NOTE: serving state uses the "state_batch" logical axis (not "batch") so
# recipes can replicate small per-token activations (2D weight-stationary TP
# at decode) without replicating the KV cache.
STATE_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    # slot cache (B, max_len, Hkv, D)
    (r"(^|/)(k|v)$", ("state_batch", "kv_seq", "kv_heads", None)),
    # paged pool (pages, page, Hkv*D): each head's D lanes are contiguous, so
    # a model axis that divides Hkv splits the minor axis at head bounds
    (r"(^|/)(k|v)$", ("state_batch", "kv_seq", "kv_heads")),
    (r"ckv$", ("state_batch", "kv_seq", None)),
    (r"krope$", ("state_batch", "kv_seq", None)),
    (r"conv$", ("state_batch", None, "lru")),
    (r"(^|/)h$", ("state_batch", "lru")),
    (r"(^|/)c$", ("state_batch", "heads", "ff", None)),
    (r"(^|/)n$", ("state_batch", "heads", "ff")),
    (r"(^|/)m$", ("state_batch", "heads")),
]

_STATE_COMPILED = [(re.compile(pat), spec) for pat, spec in STATE_RULES]


_SCANNED = re.compile(r"(^|/)scan/")


def _state_rule(s: str, leaf):
    """The rule of the leaf at path ``s``: among the rules its path matches,
    the one as long as the leaf's rank without the stack axis of a scanned
    (``scan/...``) leaf, else the first. A paged pool and a slot cache
    share their paths and differ in rank."""
    matches = [spec for pat, spec in _STATE_COMPILED if pat.search(s)]
    rank = leaf.ndim - bool(_SCANNED.search(s))
    return next((m for m in matches if len(m) == rank),
                matches[0] if matches else None)


def state_pspecs(states) -> object:
    """Pytree of PartitionSpec for a serving-state tree. Stacked (scanned)
    states get a leading replicated 'stack' dim by ndim mismatch, same as
    params. sLSTM (B, D) states match the (batch, lru) rule via trailing-dim
    truncation."""

    def annotate(path, leaf):
        spec = _state_rule(_path_str(path), leaf)
        if spec is None:
            return guarded_spec(leaf.shape, ("state_batch",))
        if len(spec) < leaf.ndim:
            spec = ("stack",) * (leaf.ndim - len(spec)) + tuple(spec)
        elif len(spec) > leaf.ndim:
            spec = ("state_batch",) + tuple(spec[len(spec) - leaf.ndim + 1:])
        return guarded_spec(leaf.shape, tuple(spec))

    return jax.tree_util.tree_map_with_path(annotate, states)


def state_shardings(states, mesh) -> object:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), state_pspecs(states))
