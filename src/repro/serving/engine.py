"""Continuous-batching serving engine over the functional model zoo.

The XaaS serving story: a SERVICE-class lease holds a fixed chip allocation;
inside it, this engine multiplexes many short FaaS-style requests onto one
compiled decode program (the paper's "fine-grained transactional computations"
running on a long-lived high-performance allocation).

Design (vLLM-shape, JAX-native):
  * fixed slot count B (the compiled decode batch) with per-slot state inside
    the *stacked* KV/recurrent caches; slots are recycled across requests
    (continuous batching).
  * a FUSED per-step program: decode, per-slot sampling (temperature/top-k
    carried as (B,) device arrays), length update, and EOS/max-token
    done-flag computation all happen inside one ``jax.jit`` — the host syncs
    a single packed "tokens | active | done" row batch per step (or one
    stacked fetch every ``sync_every`` steps). Nothing slow on the data
    path, per the paper's Invocation principle.
  * batched admission: all admissible queued requests sharing a *suffix*
    bucket prefill in ONE batched program call (batch padded to a power of
    two so the compiled-program count stays bounded at
    #buckets x log2(slots)+1). Prompts are right-padded (absolute positions
    [0, L)), so with the optional radix prefix cache
    (``prefix_cache_bytes``) admission restores the longest cached prefix
    with a jitted scatter and prefills ONLY the suffix tokens — the largest
    prefill-compute lever under shared system prompts / multi-turn traffic.
  * slot admission writes the prefilled per-slot state into the batched
    state tree with a jitted scatter (`_assign`), so admission is O(state of
    one slot), not O(whole cache).
  * all host-side logic (queueing, retirement bookkeeping) is control plane;
    every data-plane array op is jit'd. REST never touches the data path.

``fused=False`` keeps the legacy host-loop step (B scalar ``sample`` calls +
per-token ``device_get`` + per-slot length sync) as the "before" reference for
``benchmarks/serving_throughput.py``.

Each ``step()`` writes host spans named ``serve.*`` (admit, prefix, prefill,
decode, flush, sync) into the profiler's trace, on the device planes' clock,
when a profiler records; ``docs/paged-kv.md`` lists them and the step's
counters in ``stats``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import logging
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aot, hooks
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.serving import speculative
from repro.serving.block_manager import (BlockManager, PagedPrefixCache,
                                         pages_for)
from repro.serving.prefix_cache import (PrefixCache, StateOps,
                                        state_batch_axes, state_pos_axes)
from repro.serving.sampling import (SamplingConfig, SamplingParams,
                                    accept_speculative, sample, sample_batched)

__all__ = ["Request", "RequestResult", "HandoffPacket", "ServingEngine",
           "clear_program_caches"]

logger = logging.getLogger(__name__)

_NO_LIMIT = 1 << 30

# a host span of the serving step: recorded only while a profiler traces,
# about half a microsecond otherwise
_span = jax.profiler.TraceAnnotation


def _fetch(x):
    """Blocking device->host copy: the time the host waits for the device,
    as a ``serve.sync`` span."""
    with _span("serve.sync"):
        return jax.device_get(x)


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: Any  # (S,) int32 (or (K, S) audio)
    max_new_tokens: int
    sampling: SamplingConfig = dataclasses.field(default_factory=SamplingConfig)
    eos_id: int | None = None


@dataclasses.dataclass
class RequestResult:
    request_id: int
    tokens: list[int] | list[tuple]  # generated tokens (tuples for audio)
    prefill_steps: int = 1
    decode_steps: int = 0
    # per-request latency telemetry (real wall-clock seconds): time to first
    # token (submit -> first sampled token visible on the host) and total
    # decode wall time after admission. With sync_every > 1, decode_s is
    # measured at the flush that retired the request (token visibility, not
    # device completion — the honest serving-side number).
    ttft_s: float = 0.0
    decode_s: float = 0.0

    @property
    def tpot_s(self) -> float:
        """Time per output token after the first (0 for 1-token results)."""
        n = len(self.tokens)
        return self.decode_s / (n - 1) if n > 1 else 0.0


@dataclasses.dataclass
class HandoffPacket:
    """A finished prefill staged for transfer to a decode replica — the
    unit of the disaggregated fleet's KV handoff plane.

    ``pages`` are the SOURCE replica's physical page ids; the packet holds
    one ticket reference per page (``BlockManager.export_pages``) so they
    stay resident after the source slot is freed, until the handoff plane
    confirms the install (or drops the packet) and decrefs them. ``payload``
    is the device->host staged copy: one ``(max_blocks, ...)`` array per
    state leaf in deterministic tree order, of which the first
    ``pages_for(length, page_size)`` rows are real. ``shas`` hash each real
    page's content across every leaf — the destination re-hashes before
    scattering, so a corrupted transfer is rejected (and the request falls
    back to a full local prefill) instead of silently decoding garbage.
    """

    request: Request
    prompt: np.ndarray        # (S,) int32 full prompt (affinity + fallback)
    length: int               # prompt tokens resident in the pages
    first_token: int          # sampled from the prefill logits at the source
    ttft_s: float             # source-side wall TTFT (virtual time is the
                              # fleet's job)
    pages: list[int]          # source physical page ids (ticket-referenced)
    payload: list[np.ndarray]
    shas: list[str]
    nbytes: int               # real-page bytes (the transfer cost model input)


def _page_shas(payload: list[np.ndarray], npages: int) -> list[str]:
    """Per-page content hash over every state leaf's row j (leaves in
    deterministic tree order) — the handoff plane's end-to-end integrity
    check between a source gather and a destination scatter."""
    out = []
    for j in range(npages):
        h = hashlib.sha256()
        for leaf in payload:
            h.update(np.ascontiguousarray(leaf[j]).tobytes())
        out.append(h.hexdigest())
    return out


def _bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class _Programs:
    """The compiled data-plane program bundle for one (arch config, slot
    geometry, kernel-tier set).

    Every program the engine executes is pure in (params, state, ctrl), so
    nothing engine-instance-specific is baked into a trace — which means the
    bundle can be SHARED across engine instances. That is what makes a fleet
    replica boot *warm*: the first engine for a geometry pays trace+compile,
    every later replica (and every re-boot after a scale-to-zero release)
    reuses the same jitted programs, the serving analogue of the
    warm-deployment cache in ``InvocationService``.

    The cache key includes the hook binding's chosen providers: programs
    traced under one kernel tier must never serve an engine bound to another.

    Every program is registered in an :class:`repro.core.aot.AotRegistry`,
    so the bundle's compiled executables can ALSO be exported to a
    persistent ``ArtifactStore`` and re-installed in a later process — the
    IR-boot rung below this in-process warm cache (see
    ``ServingEngine.warmup``'s boot ladder and docs/ir-containers.md).
    """

    def __init__(self, cfg, slots: int, max_len: int):
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        dt = jnp.dtype(cfg.activ_dtype)
        # per-leaf slot/batch + positional axes, found structurally (the
        # shared probe in prefix_cache — same rule StateOps uses)
        state_axes = state_batch_axes(cfg, max_len, dt)
        self.state_axes = state_axes
        self.pos_axes = state_pos_axes(cfg, max_len, dt)
        self._spec_steps: dict[int, Any] = {}
        # every program below is registered here behind a shape-fingerprint
        # dispatcher, so the whole bundle can be exported to / installed
        # from a persistent artifact store (the IR-boot rung)
        self.aot = aot.AotRegistry()

        @jax.jit
        def fused_step(params, key, states, ctrl):
            """decode + sample + length update + done flags, one program."""
            active = ctrl["active"]
            lengths = ctrl["lengths"] + active.astype(jnp.int32)
            key, sub = jax.random.split(key)
            sp = SamplingParams(ctrl["temp"], ctrl["topk"])
            toks, new_states, _ = transformer.decode_and_sample(
                params, cfg, ctrl["last"], states, lengths, sub,
                lambda k, lg: sample_batched(k, lg, sp))
            gen = ctrl["gen"] + active.astype(jnp.int32)
            first = toks if toks.ndim == 1 else toks[:, 0]
            done = active & (
                (gen >= ctrl["max_new"])
                | ((ctrl["eos"] >= 0) & (first == ctrl["eos"]))
                | (lengths >= max_len))
            amask = active if toks.ndim == 1 else active[:, None]
            toks = jnp.where(amask, toks, 0)
            packed = jnp.concatenate([
                toks.reshape(slots, -1),
                active.astype(jnp.int32)[:, None],
                done.astype(jnp.int32)[:, None],
            ], axis=1)
            new_ctrl = dict(
                ctrl,
                lengths=jnp.where(done, 0, lengths),
                active=active & ~done,
                gen=gen,
                last=toks,
            )
            return key, new_states, new_ctrl, packed

        self.fused_step = self.aot.wrap("fused_step", fused_step)

        @jax.jit
        def prefill_chunk(params, tokens, states, start, lengths):
            # tokens: (N, Sc) right-padded suffix chunk ((N, K, Sc) audio);
            # states: batch state tree with any cached prefix already
            # restored at [0, start) per row; full prefill is start == 0
            return transformer.prefill_chunk(params, cfg, tokens, states,
                                             start, lengths)

        self.prefill_chunk = self.aot.wrap("prefill_chunk", prefill_chunk)

        dt_ = dt

        @functools.partial(jax.jit, static_argnums=(0,))
        def init_batch(n):
            return transformer.init_states(cfg, n, max_len, dt_)

        self.init_batch = self.aot.wrap("init_batch", init_batch,
                                        static_argnums=(0,))

        # structure-aware extract/restore programs for the prefix cache
        # (shared across engine instances like every other program here)
        self.state_ops = StateOps(cfg, max_len, dt, aot=self.aot)

        self.sample_first = self.aot.wrap("sample_first",
                                          jax.jit(sample_batched))

        @jax.jit
        def assign(states, batch_states, ctrl, src, slot, length, first_tok,
                   temp, topk, max_new, eos):
            """Scatter prefilled request `src` of a batched prefill into
            engine slot `slot`, and arm its control-block entries."""
            def put(ax, dst, s):
                row = jax.lax.dynamic_index_in_dim(s, src, ax, keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(
                    dst, row.astype(dst.dtype), slot, ax)
            new_states = jax.tree.map(put, state_axes, states, batch_states)
            new_ctrl = dict(
                ctrl,
                lengths=ctrl["lengths"].at[slot].set(length),
                active=ctrl["active"].at[slot].set(True),
                gen=ctrl["gen"].at[slot].set(1),
                temp=ctrl["temp"].at[slot].set(temp),
                topk=ctrl["topk"].at[slot].set(topk),
                max_new=ctrl["max_new"].at[slot].set(max_new),
                eos=ctrl["eos"].at[slot].set(eos),
                last=ctrl["last"].at[slot].set(first_tok),
            )
            return new_states, new_ctrl

        self.assign = self.aot.wrap("assign", assign)

        @jax.jit
        def decode(params, tokens, states, lengths):
            return transformer.decode_step(params, cfg, tokens, states, lengths)

        self.decode = self.aot.wrap("decode", decode)  # legacy (unfused) step

    # ------------------------------------------------------------------
    def spec_step_for(self, k: int):
        """The fused speculative step program for draft length ``k``,
        memoized per bundle so engines (and fleet replicas) sharing a
        geometry share the compiled verify program too."""
        prog = self._spec_steps.get(k)
        if prog is None:
            prog = self._spec_steps[k] = self.aot.wrap(
                f"spec_step_k{k}", self._build_spec_step(k))
        return prog

    def _build_spec_step(self, k: int):
        """One jitted program per speculative step: verify all K+1 positions
        for every slot, run lossless rejection sampling, truncate at
        EOS/budget/cache-capacity, and update the device control block —
        the host fetches a single packed ``tokens*|emitted|active|done``
        matrix, exactly like the plain fused step but with up to K+1 tokens
        per slot per sync.

        Rollback is free for positional state (rejected cache writes sit
        beyond the committed length mask); archs with recurrent mixers
        verify stepwise and the program rolls their non-positional leaves
        back by selecting the per-step snapshot at each row's accepted
        boundary.
        """
        cfg, slots, max_len = self.cfg, self.slots, self.max_len
        c = k + 1
        stepwise = speculative.has_recurrent_state(cfg)
        state_axes, pos_axes = self.state_axes, self.pos_axes

        @jax.jit
        def spec_step(params, key, states, ctrl, drafts, ndraft):
            active = ctrl["active"]
            length = ctrl["lengths"]
            tokens = jnp.concatenate([ctrl["last"][:, None], drafts], axis=1)
            if stepwise:
                logits, steps = transformer.verify_stepwise(
                    params, cfg, tokens, states, length, active)
            else:
                logits, new_states = transformer.verify_chunk(
                    params, cfg, tokens, states, length)
            key, sub = jax.random.split(key)
            sp = SamplingParams(ctrl["temp"], ctrl["topk"])
            out, accepted = accept_speculative(sub, logits, drafts, ndraft, sp)
            if stepwise:
                # recurrent rollback: state after processing 1 + accepted
                # tokens is the snapshot at index `accepted`; positional
                # leaves keep the final write set (masked rollback)
                sel = jnp.clip(accepted, 0, c - 1)
                bidx = jnp.arange(slots)

                def pick(ba, pa, *leaves):
                    if pa != -1:
                        return leaves[-1]
                    arr = jnp.moveaxis(jnp.stack(leaves, 0), ba + 1, 1)
                    return jnp.moveaxis(arr[sel, bidx], 0, ba)

                new_states = jax.tree.map(pick, state_axes, pos_axes, *steps)

            emit = accepted + 1
            idx = jnp.arange(c)[None, :]
            eos_hit = ((idx < emit[:, None]) & (ctrl["eos"][:, None] >= 0)
                       & (out == ctrl["eos"][:, None]))
            any_eos = eos_hit.any(axis=1)
            first_eos = jnp.argmax(eos_hit, axis=1)
            m = jnp.where(any_eos, first_eos + 1, emit)
            m = jnp.minimum(m, jnp.maximum(ctrl["max_new"] - ctrl["gen"], 1))
            m = jnp.where(active, m, 0)
            new_len = length + m
            gen = ctrl["gen"] + m
            done = active & ((gen >= ctrl["max_new"])
                             | (any_eos & (first_eos < m))
                             | (new_len >= max_len))
            out = jnp.where(idx < m[:, None], out, 0)
            last = jnp.take_along_axis(
                out, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            packed = jnp.concatenate([
                out,
                m[:, None],
                active.astype(jnp.int32)[:, None],
                done.astype(jnp.int32)[:, None],
            ], axis=1)
            new_ctrl = dict(
                ctrl,
                lengths=jnp.where(done, 0, new_len),
                active=active & ~done,
                gen=gen,
                last=last,
            )
            return key, new_states, new_ctrl, packed

        return spec_step


_PROGRAMS: dict[tuple, _Programs] = {}


def _programs_for(cfg, slots: int, max_len: int,
                  binding: hooks.Binding | None,
                  mesh_key=None) -> _Programs:
    tiers = None if binding is None else binding.tier_fingerprint()
    # mesh geometry is part of program identity: the same arch x slot
    # geometry traced under a (1,2) mesh compiles different (SPMD) programs
    # than the single-device floor, and an engine must never serve through a
    # bundle traced for another mesh
    key = (cfg, slots, max_len, tiers, mesh_key)
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = _PROGRAMS[key] = _Programs(cfg, slots, max_len)
    return prog


def paged_page_axes(cfg, page_size: int, dtype):
    """Per-leaf page axis of the paged serving-state tree (the axis whose
    extent tracks the pool's page count), found structurally the same way
    ``state_batch_axes`` finds slot axes."""
    s2 = jax.eval_shape(
        lambda: transformer.init_paged_states(cfg, 2, page_size, dtype))
    s3 = jax.eval_shape(
        lambda: transformer.init_paged_states(cfg, 3, page_size, dtype))

    def axis(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        raise AssertionError(f"paged state leaf has no page axis: {a.shape}")

    return jax.tree.map(axis, s2, s3)


class _PagedPrograms:
    """Compiled data-plane bundle for one PAGED geometry (arch config, slot
    count, max_len, page size, pool size, kernel-tier set) — the paged
    analogue of :class:`_Programs`, shared across engine instances the same
    way. The block-table array is an explicit program input, so host-side
    page remaps (growth, CoW, preemption) never retrace anything."""

    def __init__(self, cfg, slots: int, max_len: int, page_size: int,
                 num_pages: int):
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_blocks = max_len // page_size
        dt = jnp.dtype(cfg.activ_dtype)
        self.page_axes = paged_page_axes(cfg, page_size, dt)
        self._spec_steps: dict[int, Any] = {}
        self.aot = aot.AotRegistry()  # export/install, like _Programs

        @jax.jit
        def fused_step(params, key, states, ctrl, bt):
            """decode through block tables + sample + length update + done
            flags, one program (text frontend only — paged mode rejects
            audio/vlm at engine construction)."""
            active = ctrl["active"]
            lengths = ctrl["lengths"] + active.astype(jnp.int32)
            key, sub = jax.random.split(key)
            sp = SamplingParams(ctrl["temp"], ctrl["topk"])
            toks, new_states, _ = transformer.decode_and_sample(
                params, cfg, ctrl["last"], states, lengths, sub,
                lambda k, lg: sample_batched(k, lg, sp),
                block_tables=bt, page_size=page_size)
            gen = ctrl["gen"] + active.astype(jnp.int32)
            done = active & (
                (gen >= ctrl["max_new"])
                | ((ctrl["eos"] >= 0) & (toks == ctrl["eos"]))
                | (lengths >= max_len))
            toks = jnp.where(active, toks, 0)
            packed = jnp.concatenate([
                toks[:, None],
                active.astype(jnp.int32)[:, None],
                done.astype(jnp.int32)[:, None],
            ], axis=1)
            new_ctrl = dict(
                ctrl,
                lengths=jnp.where(done, 0, lengths),
                active=active & ~done,
                gen=gen,
                last=toks,
            )
            return key, new_states, new_ctrl, packed

        self.fused_step = self.aot.wrap("fused_step", fused_step)

        @jax.jit
        def prefill_chunk(params, tokens, states, start, lengths, bt):
            # tokens: (N, Sc) right-padded chunk; writes land in the shared
            # pools through per-row block tables — no per-slot scatter
            # (`_assign`) afterwards, admission is zero-copy
            return transformer.prefill_chunk(
                params, cfg, tokens, states, start, lengths,
                block_tables=bt, page_size=page_size)

        self.prefill_chunk = self.aot.wrap("prefill_chunk", prefill_chunk)

        @jax.jit
        def arm(ctrl, slot, length, first_tok, temp, topk, max_new, eos):
            """Arm a slot's control-block entries once its chunked prefill
            completes (the paged analogue of `_assign`, ctrl-only)."""
            return dict(
                ctrl,
                lengths=ctrl["lengths"].at[slot].set(length),
                active=ctrl["active"].at[slot].set(True),
                gen=ctrl["gen"].at[slot].set(1),
                temp=ctrl["temp"].at[slot].set(temp),
                topk=ctrl["topk"].at[slot].set(topk),
                max_new=ctrl["max_new"].at[slot].set(max_new),
                eos=ctrl["eos"].at[slot].set(eos),
                last=ctrl["last"].at[slot].set(first_tok),
            )

        self.arm = self.aot.wrap("arm", arm)

        @jax.jit
        def release(ctrl, slot):
            return dict(
                ctrl,
                lengths=ctrl["lengths"].at[slot].set(0),
                active=ctrl["active"].at[slot].set(False))

        self.release = self.aot.wrap("release", release)

        page_axes = self.page_axes

        @jax.jit
        def copy_page(states, src, dst):
            """Copy-on-write device op: pool[dst] <- pool[src] in every
            layer's pools (scan-stacked pools copy across all repeats)."""
            def f(ax, leaf):
                row = jax.lax.dynamic_index_in_dim(leaf, src, ax,
                                                   keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(leaf, row, dst, ax)
            return jax.tree.map(f, page_axes, states)

        self.copy_page = self.aot.wrap("copy_page", copy_page)

        @jax.jit
        def gather_pages(states, idx):
            """Stage pages OUT for a cross-replica handoff: pull the rows
            named by ``idx`` ((max_blocks,) int32, padded with the null
            page) out of every layer's pools onto a leading page axis. The
            host slices off the real rows and ships them."""
            def f(ax, leaf):
                return jnp.moveaxis(jnp.take(leaf, idx, axis=ax), ax, 0)
            return jax.tree.map(f, page_axes, states)

        self.gather_pages = self.aot.wrap("gather_pages", gather_pages)

        @jax.jit
        def scatter_pages(states, payload, idx):
            """Install handed-off pages: write payload row j into physical
            page ``idx[j]`` of every pool. Pad rows target the reserved
            null page 0, which no armed slot's length-masked attention ever
            reads."""
            def f(ax, leaf, rows):
                moved = jnp.moveaxis(leaf, ax, 0)
                return jnp.moveaxis(moved.at[idx].set(rows), 0, ax)
            return jax.tree.map(f, page_axes, states, payload)

        self.scatter_pages = self.aot.wrap("scatter_pages", scatter_pages)

        self.sample_first = self.aot.wrap("sample_first",
                                          jax.jit(sample_batched))

    # ------------------------------------------------------------------
    def spec_step_for(self, k: int):
        prog = self._spec_steps.get(k)
        if prog is None:
            prog = self._spec_steps[k] = self.aot.wrap(
                f"spec_step_k{k}", self._build_spec_step(k))
        return prog

    def _build_spec_step(self, k: int):
        """Fused speculative step through block tables. Paged mode is
        attention-family only, so the stepwise (recurrent-rollback) variant
        of `_Programs._build_spec_step` never applies: rejected cache
        writes sit beyond the committed length mask, exactly as in the
        contiguous verify path."""
        cfg, max_len, page_size = self.cfg, self.max_len, self.page_size
        c = k + 1

        @jax.jit
        def spec_step(params, key, states, ctrl, drafts, ndraft, bt):
            active = ctrl["active"]
            length = ctrl["lengths"]
            tokens = jnp.concatenate([ctrl["last"][:, None], drafts], axis=1)
            logits, new_states = transformer.verify_chunk(
                params, cfg, tokens, states, length,
                block_tables=bt, page_size=page_size)
            key, sub = jax.random.split(key)
            sp = SamplingParams(ctrl["temp"], ctrl["topk"])
            out, accepted = accept_speculative(sub, logits, drafts, ndraft, sp)
            emit = accepted + 1
            idx = jnp.arange(c)[None, :]
            eos_hit = ((idx < emit[:, None]) & (ctrl["eos"][:, None] >= 0)
                       & (out == ctrl["eos"][:, None]))
            any_eos = eos_hit.any(axis=1)
            first_eos = jnp.argmax(eos_hit, axis=1)
            m = jnp.where(any_eos, first_eos + 1, emit)
            m = jnp.minimum(m, jnp.maximum(ctrl["max_new"] - ctrl["gen"], 1))
            m = jnp.where(active, m, 0)
            new_len = length + m
            gen = ctrl["gen"] + m
            done = active & ((gen >= ctrl["max_new"])
                             | (any_eos & (first_eos < m))
                             | (new_len >= max_len))
            out = jnp.where(idx < m[:, None], out, 0)
            last = jnp.take_along_axis(
                out, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            packed = jnp.concatenate([
                out,
                m[:, None],
                active.astype(jnp.int32)[:, None],
                done.astype(jnp.int32)[:, None],
            ], axis=1)
            new_ctrl = dict(
                ctrl,
                lengths=jnp.where(done, 0, new_len),
                active=active & ~done,
                gen=gen,
                last=last,
            )
            return key, new_states, new_ctrl, packed

        return spec_step


_PAGED_PROGRAMS: dict[tuple, _PagedPrograms] = {}


def _paged_programs_for(cfg, slots: int, max_len: int, page_size: int,
                        num_pages: int,
                        binding: hooks.Binding | None,
                        role: str = "both",
                        mesh_key=None) -> _PagedPrograms:
    tiers = None if binding is None else binding.tier_fingerprint()
    # role is in the key even though the programs are role-agnostic: a
    # phase-specialized pool's bundle must contain exactly ITS programs
    # (a decode replica's persisted artifact never carries — or recompiles —
    # the prefill pool's wide chunk programs). mesh_key: see _programs_for.
    key = (cfg, slots, max_len, page_size, num_pages, tiers, role, mesh_key)
    prog = _PAGED_PROGRAMS.get(key)
    if prog is None:
        prog = _PAGED_PROGRAMS[key] = _PagedPrograms(
            cfg, slots, max_len, page_size, num_pages)
    return prog


def clear_program_caches() -> None:
    """Drop every in-process program bundle — the warm-boot cache. The next
    engine for ANY geometry re-enters the boot ladder below the warm rung
    (IR-boot if its artifact store holds the bundle, else cold). This is
    how tests and benchmarks measure cross-process boot behavior without
    forking a fresh interpreter."""
    _PROGRAMS.clear()
    _PAGED_PROGRAMS.clear()


class ServingEngine:
    """Continuous-batching engine for one deployed model.

    fused: run the whole per-step loop as one compiled program (default);
        False keeps the legacy host-side loop for before/after benchmarks.
    sync_every: fetch the packed per-step result every k fused steps (k > 1
        trades per-token latency for k-fold fewer host<->device syncs; slots
        that finish mid-window idle until the next sync).
    prefix_cache_bytes: byte budget for the radix prefix cache (None/0
        disables reuse). With a cache, admission looks up the longest cached
        prefix of each prompt, scatters its per-layer state into the batch
        with a jitted restore, prefills only the suffix, and donates the
        full-prompt state back to the tree (ref-counted while the slot
        serves, LRU-evicted under the budget).

    Prompts are RIGHT-padded into their bucket (real tokens at positions
    [0, L), pads at the tail, dropped from the caches): absolute positions
    are what make a shared token prefix produce identical state regardless
    of total prompt length — and, as a bonus, pad tokens no longer pollute
    attention the way the old left-pad layout let them.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        slots: int = 8,
        max_len: int = 512,
        prompt_buckets: tuple[int, ...] = (32, 128, 512),
        rng: jax.Array | None = None,
        fused: bool = True,
        sync_every: int = 1,
        binding: hooks.Binding | None = None,
        manifest: dict | None = None,
        prefix_cache_bytes: int | None = None,
        spec: speculative.SpecConfig | None = None,
        proposer=None,
        page_size: int | None = None,
        kv_pages: int | None = None,
        kv_watermark: float = 0.05,
        prefill_chunk_tokens: int | None = None,
        role: str = "both",
        artifact_store=None,
        mesh: jax.sharding.Mesh | None = None,
        rules: shd.Rules | None = None,
    ):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        # ---- per-deployment mesh + sharding rules: every data-plane
        # program (fused decode/sample, chunked prefill, spec verify, paged
        # KV ops) traces under `use_rules(rules, mesh)` so the model code's
        # logical-axis constraint() annotations resolve to real mesh axes.
        # mesh=None is the untouched portability floor: constraints no-op,
        # programs trace single-device, bundle keys unchanged. ----
        if mesh is not None and rules is None:
            rules = dict(shd.RULES_2D)
        if mesh is not None and int(mesh.shape.get("data", 1)) > 1:
            # a serving replica shards model/expert-parallel only; data
            # parallelism is MORE replicas (the fleet's width-vs-count
            # tradeoff), not a batch axis inside one engine
            raise ValueError(
                f"serving mesh {dict(mesh.shape)} has data axis > 1; use a "
                f"(1, M) mesh and scale replica COUNT for data parallelism")
        self.mesh = mesh
        self.rules = rules if mesh is not None else None
        self._mesh_key = shd.mesh_geometry(mesh)
        # persistent AOT artifact store (checkpoint.store.ArtifactStore or
        # None): enables the IR-boot rung of warmup()'s boot ladder —
        # compiled executables serialized by a previous process deserialize
        # here instead of re-tracing
        self.artifact_store = artifact_store
        # the deployment's hook binding: data-plane programs trace under it,
        # so the engine serves through the tiers the deployment probed+bound
        # (None = portable floor). `manifest` is the deployment's
        # specialization record, reported by warmup().
        self.binding = binding
        self.manifest = manifest
        # max_len is ALWAYS the final bucket: a prompt longer than the largest
        # configured bucket but <= max_len must land in a bucket that can hold
        # it (otherwise the pad count goes negative and jnp.pad crashes).
        self.prompt_buckets = tuple(
            sorted({b for b in prompt_buckets if b < max_len} | {max_len}))
        self.rng = rng if rng is not None else jax.random.key(0)
        self.fused = fused
        self.sync_every = max(int(sync_every), 1)
        # ---- speculative decoding (draft at admission+decode, verify in
        # the fused step, lossless rejection sampling) ----
        self.spec = spec
        self.proposer = None
        if spec is not None:
            if not fused:
                raise ValueError(
                    "speculative decoding requires the fused data plane")
            if cfg.frontend in ("audio", "vlm"):
                raise NotImplementedError(
                    f"speculative decoding unsupported for the "
                    f"{cfg.frontend!r} frontend")
            if self.sync_every > 1:
                # the proposer drafts from the emitted token history, so
                # every speculative step must sync its packed result — the
                # win is up to k+1 tokens per sync instead of k steps/sync
                logger.warning(
                    "speculative decoding overrides sync_every=%d -> 1",
                    self.sync_every)
                self.sync_every = 1
            self.proposer = proposer or speculative.make_proposer(spec, cfg)

        # ---- phase specialization (disaggregated fleets): a "prefill"
        # engine runs chunked prefill ONLY — finished prompts leave as
        # HandoffPackets on `handoff_out` instead of arming a decode slot.
        # A "decode" engine is a full engine that ADDITIONALLY admits
        # requests by installing already-computed KV pages
        # (install_handoff), which is what lets the disagg fleet fall back
        # to monolithic colocation on a decode replica when the prefill
        # pool is empty or the handoff plane backlogs. ----
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        if role != "both" and page_size is None:
            raise ValueError(
                "phase-specialized engine roles require paged KV "
                "(the handoff plane moves pages, not slot strips)")
        if role == "prefill" and spec is not None:
            raise ValueError(
                "a prefill-only engine never decodes; speculative decoding "
                "belongs to the decode pool")
        self.role = role
        self.handoff_out: deque[HandoffPacket] = deque()

        # ---- paged KV (vLLM-style): a shared page pool + per-slot block
        # tables instead of per-slot contiguous max_len cache strips, so a
        # replica's concurrency is bounded by TOKENS held, not slots*max_len.
        # page_size=None keeps the slot engine (the parity baseline). ----
        self.paged = page_size is not None
        self.page_size = page_size
        self.block_manager: BlockManager | None = None
        if self.paged:
            if not fused:
                raise ValueError("paged KV requires the fused data plane")
            if cfg.frontend in ("audio", "vlm"):
                raise NotImplementedError(
                    f"paged KV unsupported for the {cfg.frontend!r} frontend")
            if not transformer.supports_paged_kv(cfg):
                raise NotImplementedError(
                    "paged KV requires an attention-family arch (recurrent "
                    "mixers carry non-positional state that cannot be paged)")
            if max_len % page_size:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of "
                    f"page_size {page_size}")
            self.max_blocks = max_len // page_size
            if kv_pages is None:
                # full provisioning (every slot can reach max_len) — the
                # parity geometry; under-provision for the memory win
                kv_pages = slots * self.max_blocks + 1
            if kv_pages - 1 < self.max_blocks:
                raise ValueError(
                    f"kv_pages={kv_pages} cannot hold one max_len sequence "
                    f"({self.max_blocks} pages + the reserved null page)")
            self.kv_pages = kv_pages
            self.block_manager = BlockManager(
                kv_pages, page_size, watermark=kv_watermark)

        dt = jnp.dtype(cfg.activ_dtype)
        if self.paged:
            self.states = transformer.init_paged_states(
                cfg, self.kv_pages, page_size, dt)
        else:
            self.states = transformer.init_states(cfg, slots, max_len, dt)
        # device-side control block: everything the fused step needs to run
        # without consulting the host. (B,) arrays + the last sampled tokens.
        self.ctrl = {
            "lengths": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "gen": jnp.zeros((slots,), jnp.int32),
            "temp": jnp.zeros((slots,), jnp.float32),
            "topk": jnp.zeros((slots,), jnp.int32),
            "max_new": jnp.full((slots,), _NO_LIMIT, jnp.int32),
            "eos": jnp.full((slots,), -1, jnp.int32),
            "last": self._zero_tokens(slots),
        }
        if self.mesh is not None:
            # NamedSharding placement from the logical-axis rule trees:
            # params via PARAM_RULES (MoE expert weights land expert-parallel
            # on the model axis), KV pools / recurrent states via STATE_RULES
            # (kv_heads on model; slot/page axis on data). The small (B,)
            # control block replicates — it is host-mirrored every step.
            with shd.use_rules(self.rules, self.mesh):
                self.params = jax.device_put(
                    self.params, shd.param_shardings(self.params, self.mesh))
                self.states = jax.device_put(
                    self.states, shd.state_shardings(self.states, self.mesh))
            rep = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            self.ctrl = {k: jax.device_put(v, rep)
                         for k, v in self.ctrl.items()}
        # host-side slot table (control plane only)
        self.active: list[Request | None] = [None] * slots
        self.generated: list[list] = [[] for _ in range(slots)]
        self.queue: deque[Request] = deque()
        self.results: dict[int, RequestResult] = {}
        self._seen_ids: set[int] = set()
        self._pending: list[jax.Array] = []  # un-synced packed step results
        self.stats = {
            "prefills": 0,          # requests prefilled
            "prefill_calls": 0,     # batched prefill program executions
            "prefill_tokens": 0,    # padded token-positions run through prefill
            "prefill_real_tokens": 0,  # prompt positions among them
            "decode_steps": 0,      # decode programs run
            "decode_rows": 0,       # armed rows summed over those programs
            "retired": 0,
            "host_syncs_decode": 0,  # blocking device->host syncs on the decode path
            "unserved": 0,
            "prefix_hits": 0,        # admissions that reused a cached prefix
            "prefix_misses": 0,      # cache enabled but no usable prefix
            "prefix_hit_tokens": 0,  # prompt tokens restored instead of prefilled
            # ---- speculative decoding telemetry ----
            "spec_steps": 0,         # speculative verify program executions
            "spec_drafted": 0,       # draft tokens offered for verification
            "spec_accepted": 0,      # draft tokens accepted (and emitted)
            "spec_emitted": 0,       # total tokens emitted by spec steps
            "spec_positions": 0,     # decode-equivalent positions verified
                                     # (k+1 per step; rejected ones included
                                     # — the lease pays for drafted work)
            # ---- paged-KV telemetry (always present; nonzero only when
            # page_size is set) ----
            "chunk_prefill_calls": 0,  # batched chunk programs run
            "preemptions": 0,          # requests evicted to recompute
            "admit_skips": 0,          # watermark skips that let later
                                       # requests admit out of order
            # ---- disaggregation telemetry (role != "both") ----
            "handoffs_out": 0,         # finished prefills exported as packets
            "handoffs_in": 0,          # packets installed into decode slots
            "handoff_sha_rejects": 0,  # packets refused on page-sha mismatch
        }

        # ---- compiled programs: shared per (cfg, geometry, tier-set) so
        # replica boots after the first are warm (see _Programs) ----
        if self.paged:
            pprogs = _paged_programs_for(
                cfg, slots, max_len, page_size, self.kv_pages, binding,
                role=self.role, mesh_key=self._mesh_key)
            self._paged_progs = pprogs
            self._fused_step_paged = pprogs.fused_step
            self._prefill_chunk_paged = pprogs.prefill_chunk
            self._arm = pprogs.arm
            self._release_ctrl = pprogs.release
            self._copy_page = pprogs.copy_page
            self._gather_pages = pprogs.gather_pages
            self._scatter_pages = pprogs.scatter_pages
            self._sample_first = pprogs.sample_first
            self._spec_step = (pprogs.spec_step_for(spec.k)
                               if spec is not None else None)
            # device footprint of ONE page summed across every layer's
            # pools — the unit of the paged prefix cache's byte budget
            self.page_bytes = sum(
                int(np.prod(l.shape)) // l.shape[ax]
                * jnp.dtype(l.dtype).itemsize
                for l, ax in zip(jax.tree.leaves(self.states),
                                 jax.tree.leaves(pprogs.page_axes)))
            self.prefix_cache = (
                PagedPrefixCache(self.block_manager,
                                 capacity_bytes=prefix_cache_bytes,
                                 page_bytes=self.page_bytes)
                if prefix_cache_bytes else None)
        else:
            progs = _programs_for(cfg, slots, max_len, binding,
                                  mesh_key=self._mesh_key)
            self._progs = progs
            self._fused_step = progs.fused_step
            self._prefill_chunk = progs.prefill_chunk
            self._init_batch = progs.init_batch
            self._sample_first = progs.sample_first
            self._assign = progs.assign
            self._decode = progs.decode  # legacy (unfused) step

            self._spec_step = (progs.spec_step_for(spec.k)
                               if spec is not None else None)

            self.prefix_cache = (
                PrefixCache(progs.state_ops, capacity_bytes=prefix_cache_bytes)
                if prefix_cache_bytes else None)
        self._slot_pins: list = [None] * slots

        # ---- paged host-side control plane ----
        # block tables mirror: logical page j of slot i -> physical page id.
        # A slot's row stays ZERO (the null page) until its chunked prefill
        # completes and the slot is armed — so device programs running over
        # all B rows (decode, spec verify) can never write a mid-prefill
        # row's real pages.
        self._bt_host = (np.zeros((slots, self.max_blocks), np.int32)
                         if self.paged else None)
        self._bt_dev: jax.Array | None = None
        self._bt_dirty = True
        self._pages: list[list[int]] = [[] for _ in range(slots)]
        self._admitting: dict[int, dict] = {}   # slot -> chunked-prefill state
        self._admit_seq = [0] * slots           # admission order (preempt youngest)
        self._seq = 0
        self._slot_submit = [0.0] * slots       # original submit time (preempt restore)
        self._chunk_cap = (int(prefill_chunk_tokens) if prefill_chunk_tokens
                           else self.prompt_buckets[-1])
        self._chunk_widths = tuple(sorted(
            {min(b, self._chunk_cap) for b in self.prompt_buckets}))

        # host mirrors for the proposer control plane (spec mode only): the
        # per-slot token history (prompt + emitted), cache length, and
        # pending last token, kept in lockstep with the device control block
        # by the per-step packed sync
        self._hist: list[np.ndarray | None] = [None] * slots
        self._len_host = np.zeros((slots,), np.int64)
        self._last_host = np.zeros((slots,), np.int64)
        if self.proposer is not None:
            self.proposer.bind(self)
        if self.manifest is not None and spec is not None:
            # surface the acceleration mode next to the kernel tiers: the
            # operator should see HOW traffic is served from one record
            self.manifest = dict(self.manifest, speculative={
                "proposer": self.proposer.kind, "k": spec.k})
        if self.manifest is not None and self.paged:
            self.manifest = dict(self.manifest, paged_kv={
                "page_size": self.page_size,
                "kv_pages": self.kv_pages,
                "watermark_pages": self.block_manager.watermark_pages,
                "page_bytes": self.page_bytes,
                "role": self.role,
            })

        # latency bookkeeping (satellite telemetry: TTFT / decode wall)
        self._submit_s: dict[int, float] = {}
        self._slot_ttft = [0.0] * slots
        self._admit_s = [0.0] * slots

        # ---- persistent-AOT bundle identity: every field that selects a
        # distinct compiled program set. bundle_key() folds in the
        # jax/jaxlib version + platform, so environment drift invalidates
        # stored artifacts the same way a tier change does. ----
        self._aot_fields = {
            "family": f"serving:{cfg.name}",
            "kind": "paged" if self.paged else "slots",
            "role": self.role,
            "cfg": cfg,
            "slots": slots,
            "max_len": max_len,
            "prompt_buckets": self.prompt_buckets,
            "fused": self.fused,
            "tiers": (None if binding is None
                      else binding.tier_fingerprint()),
            "spec": (None if spec is None
                     else (spec.k, getattr(self.proposer, "kind", None))),
            "page_size": self.page_size,
            "kv_pages": getattr(self, "kv_pages", None),
            "chunk_widths": self._chunk_widths if self.paged else None,
            "prefix_cache": self.prefix_cache is not None,
            # mesh geometry fingerprint: IR-boot must never install an
            # executable traced for a different device grid — a (1,2)
            # bundle deserialized onto a single-device replica (or vice
            # versa) would crash or silently misplace every array
            "mesh": self._mesh_key,
        }
        self._bundle_key = aot.bundle_key(self._aot_fields)

    # ------------------------------------------------------------------
    def _bound(self):
        """Tracing/execution scope for the data plane: jit programs trace on
        first call, and the trace must happen under (a) the deployment's
        hook binding so the probed tiers actually serve traffic, and (b) the
        deployment's mesh + sharding rules so the model's logical-axis
        constraints resolve to mesh axes and every program lowers SPMD.
        Unsharded engines with no binding get a plain nullcontext — the
        portability floor stays byte-identical."""
        stack = contextlib.ExitStack()
        if self.mesh is not None:
            stack.enter_context(self.mesh)
            stack.enter_context(shd.use_rules(self.rules, self.mesh))
        if self.binding is not None:
            stack.enter_context(hooks.use(self.binding))
        return stack

    def _aot_registry(self) -> aot.AotRegistry:
        return (self._paged_progs if self.paged else self._progs).aot

    def boot_path_preview(self, *, assume_fresh_process: bool = False) -> str:
        """Which rung of the boot ladder warmup() WOULD take right now,
        without compiling anything — what the fleet's boot-cost-aware
        autoscaler consults before paying for a scale-up.

        ``assume_fresh_process`` skips the warm rung: the answer is then
        "ir" or "cold" as if no program in this process had ever compiled —
        what a virtual-time fleet uses to cost a boot whose warm/cold state
        it models itself (the in-process bundle may be hot for reasons
        outside the fleet's own history, e.g. another fleet in the same
        benchmark process)."""
        if (not assume_fresh_process
                and self._aot_registry().compiled_count() > 0):
            return "warm"
        if (self.artifact_store is not None
                and aot.AOT_AVAILABLE
                and self.artifact_store.contains(self._bundle_key)):
            return "ir"
        return "cold"

    def warmup(self) -> dict:
        """Boot the data plane through the three-rung ladder and return the
        full specialization manifest (ALWAYS a dict — even when every
        program was already a cache hit — with the boot record under
        ``"boot"``):

        1. **warm**  — the in-process program bundle already holds compiled
           executables (a previous replica of this geometry paid for them);
        2. **ir**    — the artifact store holds a bundle for this exact
           cfg x geometry x tier x spec x jax-version x platform key:
           deserialize the executables instead of re-tracing;
        3. **cold**  — trace + compile everything, then persist the bundle
           so the NEXT process IR-boots.

        Any mismatch (absent/stale/corrupt artifact, version or tier drift)
        falls through to the next rung with the reason recorded in
        ``manifest["boot"]["fallthrough"]`` — mirroring how probe-tier
        rejections are recorded per API. Programs the IR rung installed are
        never re-traced: the warmup sweep below dispatches to them by shape
        fingerprint and compiles only what is missing."""
        t0 = time.perf_counter()
        reg = self._aot_registry()
        boot: dict[str, Any] = {"path": "cold",
                                "bundle_key": self._bundle_key,
                                "fallthrough": []}
        if reg.compiled_count() > 0:
            boot["path"] = "warm"
        else:
            boot["fallthrough"].append(
                "warm: program bundle empty (first boot in this process)")
            if self.artifact_store is None:
                boot["fallthrough"].append("ir: no artifact store attached")
            elif not aot.AOT_AVAILABLE:
                boot["fallthrough"].append(
                    "ir: jax AOT serialization unavailable")
            else:
                got = self.artifact_store.get(self._bundle_key)
                if got is None:
                    reasons = [self.artifact_store.last_error
                               or "artifact missing"]
                    reasons += aot.explain_mismatch(self.artifact_store,
                                                    self._aot_fields)
                    boot["fallthrough"].extend(f"ir: {r}" for r in reasons)
                else:
                    blobs, _meta = got
                    installed, errors = reg.install(blobs)
                    boot["fallthrough"].extend(f"ir: {e}" for e in errors)
                    if installed > 0:
                        boot["path"] = "ir"
                    else:
                        boot["fallthrough"].append(
                            "ir: artifact held no installable programs")
        compiles_before = reg.compile_count()
        with self._bound():
            self._warmup_programs()
        boot["warmup_compiles"] = reg.compile_count() - compiles_before
        if (self.artifact_store is not None and aot.AOT_AVAILABLE
                and boot["path"] != "warm" and boot["warmup_compiles"] > 0):
            # cold rung persists; an IR boot that still had to compile some
            # programs tops the artifact up for the next process
            boot["persisted"] = self.persist_programs().get("persisted", 0)
        boot["programs"] = reg.counts()
        boot["boot_s"] = round(time.perf_counter() - t0, 6)
        manifest = dict(self.manifest) if self.manifest else {}
        manifest["boot"] = boot
        self.manifest = manifest
        tiers = {a: c["provider"]
                 for a, c in manifest.get("apis", {}).items()}
        logger.info("serving warm [%s @ %s] boot=%s (%.2fs): %s",
                    manifest.get("container", "?"),
                    manifest.get("profile", "?"),
                    boot["path"], boot["boot_s"], tiers)
        return self.manifest

    def persist_programs(self) -> dict:
        """Serialize every compiled executable of this bundle into the
        artifact store under the bundle key. Called automatically at the
        end of a cold (or partially-cold) warmup; call it again after
        serving traffic to also capture shapes warmup's sweep missed."""
        if self.artifact_store is None:
            return {"persisted": 0, "reason": "no artifact store attached"}
        if not aot.AOT_AVAILABLE:
            return {"persisted": 0,
                    "reason": "jax AOT serialization unavailable"}
        reg = self._aot_registry()
        blobs = reg.export()
        if not blobs:
            return {"persisted": 0, "reason": "no serializable executables"}
        meta = {
            "fields": aot.canonical_fields(self._aot_fields),
            "programs": sorted({k.rpartition("@")[0] for k in blobs}),
        }
        self.artifact_store.put(self._bundle_key, blobs, meta=meta)
        return {"persisted": len(blobs)}

    def _warmup_programs(self) -> None:
        if self.paged:
            self._warmup_paged()
            return
        if self.fused:
            self._fused_step(self.params, self.rng, self.states, self.ctrl)
            if self.spec is not None:
                # verify program (outputs discarded, engine state untouched)
                # + the proposer's own programs (draft prefill/decode loop)
                self._spec_step(
                    self.params, self.rng, self.states, self.ctrl,
                    jnp.zeros((self.slots, self.spec.k), jnp.int32),
                    jnp.zeros((self.slots,), jnp.int32))
                self.proposer.warmup()
        else:
            self._decode(self.params, self.ctrl["last"], self.states,
                         self.ctrl["lengths"])
        npads, n = [], 1
        top = _pow2(self.slots) if self.fused else 1
        while n <= top:
            npads.append(n)
            n <<= 1
        key = jax.random.key(0)
        zero_tok = self._zero_tokens(1)[0]
        for npad in npads:
            states = self._init_batch(npad)
            start = jnp.zeros((npad,), jnp.int32)
            lens = jnp.ones((npad,), jnp.int32)
            for sb in self.prompt_buckets:
                if self.cfg.frontend == "audio":
                    toks = jnp.zeros((npad, self.cfg.num_codebooks, sb), jnp.int32)
                else:
                    toks = jnp.zeros((npad, sb), jnp.int32)
                logits, bstates, _ = self._prefill_chunk(
                    self.params, toks, states, start, lens)
            self._sample_first(
                key, logits, SamplingParams.from_configs([SamplingConfig()] * npad))
            self._assign(self.states, bstates, self.ctrl, 0, 0, 0, zero_tok,
                         0.0, 0, _NO_LIMIT, -1)
            if self.prefix_cache is not None:
                # prefix-cache device ops: one extract/restore program per
                # pow2 block length per batch geometry
                ops = self.prefix_cache.ops
                p, zero = 1, jnp.int32(0)
                while p <= self.max_len:
                    blk = ops.extract_pos(p, bstates, zero, zero)
                    ops.restore_pos(p, states, blk, zero, zero, zero)
                    p <<= 1
                ops.restore_snap(states, ops.extract_snap(bstates, zero), zero)
        jax.block_until_ready(self.states)

    def _warmup_paged(self) -> None:
        """Pre-compile the paged data plane: the fused step, the spec
        verify step, each (pow2 batch, chunk width) prefill program, the
        ctrl arm/release ops, and the CoW page copy. All outputs are
        discarded; writes land on the null page (zero block tables)."""
        bt = jnp.zeros((self.slots, self.max_blocks), jnp.int32)
        self._fused_step_paged(self.params, self.rng, self.states, self.ctrl,
                               bt)
        if self.spec is not None:
            self._spec_step(self.params, self.rng, self.states, self.ctrl,
                            jnp.zeros((self.slots, self.spec.k), jnp.int32),
                            jnp.zeros((self.slots,), jnp.int32), bt)
            self.proposer.warmup()
        key = jax.random.key(0)
        n = 1
        while n <= _pow2(self.slots):
            start = jnp.zeros((n,), jnp.int32)
            lens = jnp.ones((n,), jnp.int32)
            sbt = jnp.zeros((n, self.max_blocks), jnp.int32)
            for cw in self._chunk_widths:
                toks = jnp.zeros((n, cw), jnp.int32)
                logits, _, _ = self._prefill_chunk_paged(
                    self.params, toks, self.states, start, lens, sbt)
            self._sample_first(
                key, logits,
                SamplingParams.from_configs([SamplingConfig()] * n))
            n <<= 1
        self._arm(self.ctrl, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                  jnp.float32(0.0), jnp.int32(0), jnp.int32(_NO_LIMIT),
                  jnp.int32(-1))
        self._release_ctrl(self.ctrl, jnp.int32(0))
        self._copy_page(self.states, jnp.int32(0), jnp.int32(0))
        if self.role == "prefill":
            # handoff staging: the prefill pool's only extra program.
            # Monolithic engines skip both handoff programs — a colocation
            # fallback never runs them either (it prefills locally), so
            # their bundles stay exactly as before this feature existed.
            self._gather_pages(self.states,
                               jnp.zeros((self.max_blocks,), jnp.int32))
        elif self.role == "decode":
            # install scatter only: a decode replica must never compile (or
            # persist) the prefill pool's staging program
            self._scatter_pages(self.states, self._payload_zeros(),
                                jnp.zeros((self.max_blocks,), jnp.int32))
        jax.block_until_ready(self.states)

    # ------------------------------------------------------------------
    def _zero_tokens(self, n: int):
        if self.cfg.frontend == "audio":
            return jnp.zeros((n, self.cfg.num_codebooks), jnp.int32)
        return jnp.zeros((n,), jnp.int32)

    def submit(self, req: Request) -> None:
        s = np.asarray(req.prompt).shape[-1]
        if s > self.max_len:
            raise ValueError(f"prompt {s} > engine max_len {self.max_len}")
        if req.request_id in self._seen_ids:
            # a duplicate would silently overwrite its results entry and
            # corrupt downstream token metering deltas
            raise ValueError(f"duplicate request_id {req.request_id}")
        self._seen_ids.add(req.request_id)
        self._submit_s[req.request_id] = time.perf_counter()
        self.queue.append(req)

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    # ---- proposer protocol: host mirrors of the device control block ----
    def history(self, slot: int) -> np.ndarray:
        """Prompt + every emitted token of the request in ``slot`` (the last
        entry is the pending token the next verify step will process)."""
        return self._hist[slot]

    def last_tokens(self) -> np.ndarray:
        """(B,) pending last token per slot (garbage for free slots)."""
        return self._last_host

    def cache_lengths(self) -> np.ndarray:
        """(B,) committed cache lengths per slot (mirrors ctrl['lengths'])."""
        return self._len_host

    # ------------------------------------------------------------------
    # Admission: longest-cached-prefix lookup -> restore -> suffix-only
    # batched prefill, one program call per suffix bucket
    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """Prefill queued requests into free slots, one batched prefill call
        per suffix bucket (legacy mode admits one request per call, matching
        the seed engine's behavior for before/after comparison).

        Requests that retire *at* admission (max_new_tokens <= 1, or no
        decode room) never occupy a slot, so the loop keeps refilling from
        the queue until the slots are saturated or the queue drains — a
        retired-at-admission request must not cost a slot a full engine
        step of idleness.
        """
        if self.paged:
            self._admit_paged()
            return
        while True:
            free = self._free_slots()
            take = min(len(free), len(self.queue))
            if not take:
                return
            entries = []
            for _ in range(take):
                req = self.queue.popleft()
                entries.append((req,) + self._lookup_prefix(req))
            groups: dict[int, list[tuple]] = {}
            for e in entries:
                req, _, start = e
                suffix = np.asarray(req.prompt).shape[-1] - start
                groups.setdefault(
                    _bucket(suffix, self.prompt_buckets), []).append(e)
            for sc, es in groups.items():
                for batch in ([es] if self.fused else [[e] for e in es]):
                    with _span("serve.prefill", rows=len(batch),
                               npad=_pow2(len(batch)), cw=sc):
                        self._admit_group(sc, batch, free)

    def _lookup_prefix(self, req: Request):
        """-> (match, start): the longest usable cached prefix and the pin
        protecting it through admission (start == 0: miss / disabled)."""
        if self.prefix_cache is None:
            return None, 0
        prompt = np.asarray(req.prompt, np.int32)
        # always prefill at least the last prompt token: its logits seed the
        # first sampled token
        with _span("serve.prefix"):
            match = self.prefix_cache.match(prompt, limit=prompt.shape[-1] - 1)
        if match.usable <= 0:
            self.stats["prefix_misses"] += 1
            return None, 0
        self.prefix_cache.acquire(match.path[-1][0])  # pin through admission
        self.stats["prefix_hits"] += 1
        self.stats["prefix_hit_tokens"] += match.usable
        return match, match.usable

    def _admit_group(self, sc: int, entries: list[tuple], free: list[int]) -> None:
        n = len(entries)
        npad = _pow2(n)  # bound compiled-program count per bucket
        if self.cfg.frontend == "audio":
            batch = np.zeros((npad, self.cfg.num_codebooks, sc), np.int32)
        else:
            batch = np.zeros((npad, sc), np.int32)
        starts = np.zeros((npad,), np.int32)
        lens = np.ones((npad,), np.int32)  # pad rows: 1 valid pos at start 0
        bstates = self._init_batch(npad)
        for i, (req, match, start) in enumerate(entries):
            prompt = np.asarray(req.prompt, np.int32)
            # right-pad: real suffix at the front, absolute positions
            # [start, L) — see the class docstring for why
            batch[i, ..., : prompt.shape[-1] - start] = prompt[..., start:]
            starts[i] = start
            lens[i] = prompt.shape[-1]
            if start > 0:
                # restore re-walks the radix tree itself: `match` may be
                # stale if an earlier group's insert split a node on its path
                with _span("serve.prefix"):
                    bstates = self.prefix_cache.restore(
                        prompt, bstates, i, start)
        logits, bstates, _ = self._prefill_chunk(
            self.params, jnp.asarray(batch), bstates,
            jnp.asarray(starts), jnp.asarray(lens))
        self.stats["prefill_calls"] += 1
        self.stats["prefills"] += n
        self.stats["prefill_tokens"] += npad * sc
        self.stats["prefill_real_tokens"] += int((lens - starts)[:n].sum())

        pad_cfg = [e[0].sampling for e in entries] \
            + [SamplingConfig()] * (npad - n)
        self.rng, sub = jax.random.split(self.rng)
        first = self._sample_first(sub, logits, SamplingParams.from_configs(pad_cfg))
        first_host = np.asarray(_fetch(first))
        now = time.perf_counter()

        for i, (req, match, start) in enumerate(entries):
            ttft = now - self._submit_s.pop(req.request_id, now)
            plen = int(np.asarray(req.prompt).shape[-1])
            pin = None
            if self.prefix_cache is not None:
                # donate the full-prompt state back to the radix tree and
                # swap the admission pin for one on the (deeper) donated node
                with _span("serve.prefix"):
                    pin = self.prefix_cache.acquire(
                        self.prefix_cache.insert(req.prompt, bstates, i, match))
                if match is not None:
                    self.prefix_cache.release(match.path[-1][0])
            # prefill token + decode steps until the cache fills at max_len
            room = self.max_len - plen + 1
            if room < req.max_new_tokens:
                logger.warning(
                    "request %s: prompt length %d leaves room for %d of the "
                    "%d requested tokens (engine max_len=%d) — output will "
                    "be truncated", req.request_id, plen, room,
                    req.max_new_tokens, self.max_len)
            if req.max_new_tokens <= 1 or room <= 1:
                # the prefill logits already yielded the only (or only
                # representable) token; retire without occupying a decode slot
                self.results[req.request_id] = RequestResult(
                    request_id=req.request_id,
                    tokens=[self._row_out(first_host[i])],
                    decode_steps=0, ttft_s=ttft)
                self.stats["retired"] += 1
                if pin is not None:
                    self.prefix_cache.release(pin)
                continue
            slot = free.pop(0)
            self.states, self.ctrl = self._assign(
                self.states, bstates, self.ctrl, i, slot, plen, first[i],
                float(req.sampling.temperature), int(req.sampling.top_k),
                int(req.max_new_tokens),
                -1 if req.eos_id is None else int(req.eos_id))
            self.active[slot] = req
            self.generated[slot] = [self._row_out(first_host[i])]
            self._slot_pins[slot] = pin
            self._slot_ttft[slot] = ttft
            self._admit_s[slot] = now
            if self.spec is not None:
                prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                self._hist[slot] = np.concatenate(
                    [prompt, [np.int32(first_host[i])]])
                self._len_host[slot] = plen
                self._last_host[slot] = int(first_host[i])
                self.proposer.admit(slot, prompt)

    def _row_out(self, row: np.ndarray):
        return tuple(int(x) for x in row) if row.ndim else int(row)

    # ------------------------------------------------------------------
    # Paged admission + chunked prefill + page growth/CoW/preemption
    # ------------------------------------------------------------------
    def _admit_paged(self) -> None:
        """Paged admission: reference the longest cached prefix's pages
        into a fresh block table (aliasing, not copying), allocate fresh
        pages for the rest of the prompt, and hand the slot to the chunked
        prefiller. Admission is OUT OF ORDER under the page watermark: a
        large request that cannot allocate yet is skipped (not blocking),
        so smaller requests behind it keep the replica busy — the
        head-of-line starvation fix (see stats['admit_skips'])."""
        free = self._free_slots()
        if not free or not self.queue:
            return
        bm = self.block_manager
        ps = self.page_size
        kept: deque[Request] = deque()
        for _ in range(len(self.queue)):
            if not free:
                break
            req = self.queue.popleft()
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            plen = int(prompt.shape[-1])
            match = None
            if self.prefix_cache is not None:
                with _span("serve.prefix"):
                    match = self.prefix_cache.match(prompt, limit=plen - 1)
            start = match.usable if match is not None else 0
            # budget: fresh pages past the shared FULL pages — a shared
            # PARTIAL tail page is copied (not aliased) right here, so the
            # check below reserves its replacement too
            need = pages_for(plen, ps) - start // ps
            if not bm.can_alloc(need, respect_watermark=True):
                # pages held ONLY by the prefix cache are best-effort memory:
                # evict them on demand rather than stall admission. An IDLE
                # engine additionally ignores the watermark — it only
                # arbitrates between concurrent tenants, and nothing running
                # means nothing will ever free pages for us.
                idle = not kept and all(r is None for r in self.active)
                if self.prefix_cache is not None:
                    with _span("serve.prefix"):
                        self.prefix_cache.reclaim(need + bm.watermark_pages)
                        # eviction may have dropped the matched branch:
                        # re-match before touching its page refs
                        match = self.prefix_cache.match(prompt,
                                                        limit=plen - 1)
                    start = match.usable
                    need = pages_for(plen, ps) - start // ps
                if not bm.can_alloc(need, respect_watermark=not idle):
                    self.stats["admit_skips"] += 1
                    kept.append(req)
                    continue
            slot = free.pop(0)
            shared = list(match.pages) if start > 0 else []
            bm.incref(shared)
            if start % ps:
                # copy-on-write the shared partial tail page NOW: the
                # remaining prompt prefills into an owned page, and no
                # mid-prefill CoW can run out of pool later
                tail = shared[-1]
                new = bm.cow(tail)  # consumes OUR ref on `tail`
                self.states = self._copy_page(
                    self.states, jnp.int32(tail), jnp.int32(new))
                shared[-1] = new
            fresh = bm.alloc(pages_for(plen, ps) - len(shared))
            self._pages[slot] = shared + fresh
            self.active[slot] = req
            self.generated[slot] = []
            self._seq += 1
            self._admit_seq[slot] = self._seq
            self._slot_submit[slot] = self._submit_s.get(
                req.request_id, time.perf_counter())
            self._admitting[slot] = {"prompt": prompt, "plen": plen,
                                     "pos": start}
            if self.prefix_cache is not None:
                if start > 0:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += start
                else:
                    self.stats["prefix_misses"] += 1
        self.queue.extendleft(reversed(kept))

    def _prefill_step_paged(self) -> None:
        """Advance every mid-prefill slot by one batched chunk: ONE
        compiled program per engine step regardless of how many rows are
        admitting, interleaved with the decode step that follows — chunked
        prefill never stalls in-flight decodes for a whole prompt. Rows
        whose prompt completes sample their first token from the chunk's
        logits (the chunk program returns logits at each row's last real
        position) and arm the device control block."""
        rows = sorted(self._admitting)
        if not rows:
            return
        remaining = max(self._admitting[s]["plen"] - self._admitting[s]["pos"]
                        for s in rows)
        cw = _bucket(min(remaining, self._chunk_cap), self._chunk_widths)
        npad = _pow2(len(rows))
        with _span("serve.prefill", rows=len(rows), npad=npad, cw=cw):
            self._run_chunk(rows, cw, npad)

    def _run_chunk(self, rows: list[int], cw: int, npad: int) -> None:
        """One chunk of ``cw`` positions for the mid-prefill slots ``rows``,
        batched to ``npad`` rows, then first tokens and arming for the
        prompts it completes."""
        ps = self.page_size
        n = len(rows)
        toks = np.zeros((npad, cw), np.int32)
        starts = np.zeros((npad,), np.int32)
        lens = np.ones((npad,), np.int32)  # pad rows: 1 pos on the null page
        bt = np.zeros((npad, self.max_blocks), np.int32)
        real = 0
        for i, s in enumerate(rows):
            st = self._admitting[s]
            w = min(cw, st["plen"] - st["pos"])
            # CoW the shared partial tail page of a restored prefix before
            # this chunk writes into it (admitting slots are never preempted,
            # so the slot survives)
            self._prepare_write(s, st["pos"], st["pos"] + w)
            toks[i, :w] = st["prompt"][st["pos"]: st["pos"] + w]
            real += w
            starts[i] = st["pos"]
            lens[i] = st["pos"] + w
            bt[i, : len(self._pages[s])] = self._pages[s]
        logits, self.states, _ = self._prefill_chunk_paged(
            self.params, jnp.asarray(toks), self.states,
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(bt))
        self.stats["prefill_calls"] += 1
        self.stats["chunk_prefill_calls"] += 1
        self.stats["prefill_tokens"] += npad * cw
        self.stats["prefill_real_tokens"] += real

        fin = [i for i, s in enumerate(rows)
               if int(lens[i]) >= self._admitting[s]["plen"]]
        if not fin:
            for i, s in enumerate(rows):
                self._admitting[s]["pos"] = int(lens[i])
            return
        pad_cfg = [self.active[s].sampling for s in rows] \
            + [SamplingConfig()] * (npad - n)
        self.rng, sub = jax.random.split(self.rng)
        first = self._sample_first(sub, logits,
                                   SamplingParams.from_configs(pad_cfg))
        first_host = np.asarray(_fetch(first))
        now = time.perf_counter()
        for i, s in enumerate(rows):
            st = self._admitting[s]
            if int(lens[i]) < st["plen"]:
                st["pos"] = int(lens[i])
                continue
            # ---- prompt complete: donate pages to the prefix cache, arm ----
            del self._admitting[s]
            req = self.active[s]
            plen = st["plen"]
            tok = int(first_host[i])
            self.stats["prefills"] += 1
            ttft = now - self._submit_s.pop(req.request_id, now)
            if self.prefix_cache is not None:
                with _span("serve.prefix"):
                    self.prefix_cache.insert(
                        st["prompt"], self._pages[s][: pages_for(plen, ps)])
            room = self.max_len - plen + 1
            if room < req.max_new_tokens:
                logger.warning(
                    "request %s: prompt length %d leaves room for %d of the "
                    "%d requested tokens (engine max_len=%d) — output will "
                    "be truncated", req.request_id, plen, room,
                    req.max_new_tokens, self.max_len)
            if req.max_new_tokens <= 1 or room <= 1:
                # prefill logits already yielded the only token; retire
                # without ever occupying a decode step
                self.results[req.request_id] = RequestResult(
                    request_id=req.request_id, tokens=[tok],
                    decode_steps=0, ttft_s=ttft)
                self.stats["retired"] += 1
                self.block_manager.decref(self._pages[s])
                self._pages[s] = []
                self.active[s] = None
                continue
            if self.role == "prefill":
                # phase boundary: this engine's job ends at the first
                # token. The request leaves as a handoff packet (pages
                # ticket-referenced, slot freed) instead of arming a
                # decode slot it does not have.
                self.handoff_out.append(
                    self._export_handoff(s, st, tok, ttft))
                continue
            self.ctrl = self._arm(
                self.ctrl, jnp.int32(s), jnp.int32(plen), jnp.int32(tok),
                jnp.float32(req.sampling.temperature),
                jnp.int32(req.sampling.top_k),
                jnp.int32(req.max_new_tokens),
                jnp.int32(-1 if req.eos_id is None else req.eos_id))
            self.generated[s] = [tok]
            self._slot_ttft[s] = ttft
            self._admit_s[s] = now
            self._len_host[s] = plen
            self._last_host[s] = tok
            self._bt_host[s, :] = 0
            self._bt_host[s, : len(self._pages[s])] = self._pages[s]
            self._bt_dirty = True
            if self.spec is not None:
                self._hist[s] = np.concatenate([st["prompt"], [np.int32(tok)]])
                self.proposer.admit(s, st["prompt"])

    # ------------------------------------------------------------------
    # KV page handoff (disaggregated fleets): prefill engines stage
    # finished prompts out; decode engines admit by installing the pages
    # ------------------------------------------------------------------
    def _payload_zeros(self):
        """A zero handoff payload pytree ((max_blocks, ...) per state leaf)
        — the scatter program's warmup argument."""
        def z(ax, leaf):
            shape = list(leaf.shape)
            shape.pop(ax)
            return jnp.zeros((self.max_blocks, *shape), leaf.dtype)
        return jax.tree.map(z, self._paged_progs.page_axes, self.states)

    def _export_handoff(self, slot: int, st: dict, tok: int,
                        ttft: float) -> HandoffPacket:
        """Stage slot ``slot``'s finished prefill for transfer: take a
        ticket reference per page (the pages survive the slot being freed),
        gather them device->host, hash each page, and free the slot. The
        returned packet owns the request from here — the handoff plane
        decrefs the ticket references after the destination installs (or
        the packet is dropped)."""
        req = self.active[slot]
        plen = st["plen"]
        pages = self._pages[slot][: pages_for(plen, self.page_size)]
        self.block_manager.export_pages(pages)  # the ticket's own refs
        idx = np.zeros((self.max_blocks,), np.int32)
        idx[: len(pages)] = pages
        gathered = self._gather_pages(self.states, jnp.asarray(idx))
        with _span("serve.sync"):
            payload = [np.asarray(jax.device_get(l))
                       for l in jax.tree.leaves(gathered)]
        packet = HandoffPacket(
            request=req, prompt=st["prompt"], length=plen,
            first_token=tok, ttft_s=ttft, pages=list(pages),
            payload=payload, shas=_page_shas(payload, len(pages)),
            nbytes=len(pages) * self.page_bytes)
        # the slot's own references drop now; only the ticket's remain
        self.block_manager.decref(self._pages[slot])
        self._pages[slot] = []
        self.active[slot] = None
        self.generated[slot] = []
        self.stats["handoffs_out"] += 1
        return packet

    def release_handoff(self, packet: HandoffPacket) -> None:
        """Drop the ticket references a packet holds on THIS engine's pages
        — called by the handoff plane once the destination confirmed the
        install, or when the packet is abandoned. This is the cross-replica
        half of the refcount invariant: every export_pages incref is undone
        by exactly one release."""
        self.block_manager.decref(packet.pages)

    def can_install(self, packet: HandoffPacket) -> bool:
        """Whether a handoff could install right now: a free slot that is
        not mid-prefill, plus pool room for the packet's pages under the
        same watermark discipline as fresh admission (prefix-cache pages
        are reclaimable; an idle engine ignores the watermark)."""
        if self.role == "prefill" or not self.paged:
            return False
        if not self._free_slots():
            return False
        need = len(packet.pages)
        bm = self.block_manager
        if bm.can_alloc(need, respect_watermark=True):
            return True
        if self.prefix_cache is not None:
            self.prefix_cache.reclaim(need + bm.watermark_pages)
        idle = not self.queue and all(r is None for r in self.active)
        return bm.can_alloc(need, respect_watermark=not idle)

    def install_handoff(self, packet: HandoffPacket) -> bool:
        """Admit a request by INSTALLING its already-computed KV pages: the
        decode-pool admission path. Verifies the per-page shas against the
        staged payload, allocates fresh physical pages
        (``BlockManager.install_pages``), scatters the payload into them,
        and arms the slot exactly as a local prefill completion would —
        same first token, same absolute positions, so the greedy stream is
        byte-identical to the monolithic engine's. Returns False (with no
        state touched beyond best-effort cache reclaim) when verification
        fails or there is no room; the caller re-queues or falls back."""
        if _page_shas(packet.payload, len(packet.pages)) != packet.shas:
            self.stats["handoff_sha_rejects"] += 1
            return False
        if not self.can_install(packet):
            return False
        req = packet.request
        plen = packet.length
        tok = int(packet.first_token)
        npg = len(packet.pages)
        slot = self._free_slots()[0]
        ids = self.block_manager.install_pages(npg)
        idx = np.zeros((self.max_blocks,), np.int32)
        idx[:npg] = ids
        payload = jax.tree.unflatten(
            jax.tree.structure(self._paged_progs.page_axes),
            [jnp.asarray(a) for a in packet.payload])
        self.states = self._scatter_pages(self.states, payload,
                                          jnp.asarray(idx))
        self._pages[slot] = list(ids)
        self.active[slot] = req
        self._seen_ids.add(req.request_id)
        self._seq += 1
        self._admit_seq[slot] = self._seq
        now = time.perf_counter()
        self._slot_submit[slot] = now
        self.ctrl = self._arm(
            self.ctrl, jnp.int32(slot), jnp.int32(plen), jnp.int32(tok),
            jnp.float32(req.sampling.temperature),
            jnp.int32(req.sampling.top_k),
            jnp.int32(req.max_new_tokens),
            jnp.int32(-1 if req.eos_id is None else req.eos_id))
        self.generated[slot] = [tok]
        self._slot_ttft[slot] = packet.ttft_s
        self._admit_s[slot] = now
        self._len_host[slot] = plen
        self._last_host[slot] = tok
        self._bt_host[slot, :] = 0
        self._bt_host[slot, :npg] = ids
        self._bt_dirty = True
        if self.prefix_cache is not None:
            # the handed-off prompt seeds THIS replica's radix tree, so
            # session followers and shared-prefix siblings routed here by
            # affinity hit locally instead of re-prefilling
            self.prefix_cache.insert(packet.prompt, ids)
        if self.spec is not None:
            self._hist[slot] = np.concatenate(
                [packet.prompt, [np.int32(tok)]])
            self.proposer.admit(slot, packet.prompt)
        self.stats["handoffs_in"] += 1
        return True

    # ------------------------------------------------------------------
    def _bt_device(self) -> jax.Array:
        if self._bt_dirty or self._bt_dev is None:
            self._bt_dev = jnp.asarray(self._bt_host)
            self._bt_dirty = False
        return self._bt_dev

    def _youngest_decoding(self) -> int | None:
        cands = [s for s, r in enumerate(self.active)
                 if r is not None and s not in self._admitting]
        return max(cands, key=lambda s: self._admit_seq[s]) if cands else None

    def _reclaim(self, n: int) -> bool:
        """Evict prefix-cache pages until ``n`` are allocatable; False when
        there is no cache or it cannot free enough."""
        if self.prefix_cache is None:
            return False
        with _span("serve.prefix"):
            return self.prefix_cache.reclaim(n)

    def _reclaim_or_preempt(self, n: int) -> int | None:
        """Make ``n`` pages allocatable: evict prefix-cache pages first
        (cold reuse state is the cheapest thing to give back), then preempt
        the YOUNGEST decoding slot (its recompute loses the least work).
        Returns the preempted slot, or None when cache eviction sufficed;
        raises when nothing is left to take."""
        if self._reclaim(n):
            return None
        victim = self._youngest_decoding()
        if victim is None:
            raise RuntimeError(
                "KV page pool exhausted with nothing left to preempt")
        self._preempt(victim)
        return victim

    def _prepare_write(self, slot: int, lo: int, hi: int) -> bool:
        """Make cache positions [lo, hi) of ``slot`` writable: grow its
        block table to cover ``hi`` entries and copy-on-write any shared
        page in the write range. May preempt other slots under pool
        pressure — or, at the last resort, ``slot`` itself, in which case
        this returns False and the caller skips the slot's step."""
        bm, ps = self.block_manager, self.page_size
        pages = self._pages[slot]
        while True:
            need = pages_for(hi, ps) - len(pages)
            if need <= 0:
                break
            if bm.can_alloc(need):
                fresh = bm.alloc(need)
                base = len(pages)
                pages.extend(fresh)
                if slot not in self._admitting:
                    self._bt_host[slot, base: base + need] = fresh
                    self._bt_dirty = True
                break
            if self._reclaim_or_preempt(need) == slot:
                return False
        if hi > lo:
            for j in range(lo // ps, (hi - 1) // ps + 1):
                # re-check the ref each round: a cache eviction can DE-SHARE
                # this very page (making the copy unnecessary) without
                # freeing anything
                while bm.ref[pages[j]] > 1:
                    if bm.can_alloc(1):
                        pid = pages[j]
                        new = bm.cow(pid)
                        self.states = self._copy_page(
                            self.states, jnp.int32(pid), jnp.int32(new))
                        pages[j] = new
                        if slot not in self._admitting:
                            self._bt_host[slot, j] = new
                            self._bt_dirty = True
                        break
                    if self._reclaim(1):
                        continue
                    if bm.ref[pages[j]] <= 1:
                        break
                    victim = self._youngest_decoding()
                    if victim is None:
                        raise RuntimeError(
                            "KV page pool exhausted with nothing left to "
                            "preempt")
                    self._preempt(victim)
                    if victim == slot:
                        return False
        return True

    def _preempt(self, slot: int) -> None:
        """Preemption by recompute (the vLLM policy): release the victim's
        pages and push its request back to the FRONT of the queue; it
        re-admits (reusing whatever prefix is still cached) once pages free
        up. Generated tokens are discarded — recomputation replays the same
        stream for greedy sampling. Buffered step results are flushed first
        so a later sync cannot credit old tokens to the slot's next
        tenant."""
        self._flush()
        req = self.active[slot]
        if req is None:
            return  # the flush retired it — its pages are already free
        self.block_manager.decref(self._pages[slot])
        self._pages[slot] = []
        self.active[slot] = None
        self.generated[slot] = []
        self._admitting.pop(slot, None)
        self._bt_host[slot, :] = 0
        self._bt_dirty = True
        self.ctrl = self._release_ctrl(self.ctrl, jnp.int32(slot))
        if self.spec is not None:
            self._hist[slot] = None
            self.proposer.retire(slot)
        # restore the original submit time so TTFT honestly includes the wait
        self._submit_s[req.request_id] = self._slot_submit[slot]
        self.queue.appendleft(req)
        self.stats["preemptions"] += 1

    def _step_fused_paged(self) -> None:
        """The paged decode step: grow/CoW every armed slot's write
        position, then run ONE fused program over all B rows through the
        device block tables. Mid-prefill rows ride along on the null page
        (ctrl-inactive, zero block-table rows)."""
        with _span("serve.decode") as span:
            for s in range(self.slots):
                if self.active[s] is None or s in self._admitting:
                    continue
                length = int(self._len_host[s])
                if length >= self.max_len:
                    continue
                self._prepare_write(s, length, length + 1)
            armed = [s for s, r in enumerate(self.active)
                     if r is not None and s not in self._admitting]
            if not armed:
                return
            span.set_metadata(rows=len(armed))
            self.rng, self.states, self.ctrl, packed = self._fused_step_paged(
                self.params, self.rng, self.states, self.ctrl,
                self._bt_device())
            self.stats["decode_steps"] += 1
            self.stats["decode_rows"] += len(armed)
            for s in armed:
                # pessimistic host mirror: rows that hit done mid-window stop
                # advancing on device; the flush reconciles (the extra page a
                # stale +1 can allocate is freed at retire)
                self._len_host[s] = min(int(self._len_host[s]) + 1,
                                        self.max_len)
            self._pending.append(packed)
        if len(self._pending) >= self.sync_every or all(
            len(self.generated[i]) + len(self._pending) >= r.max_new_tokens
            for i, r in enumerate(self.active)
            if r is not None and i not in self._admitting
        ):
            self._flush()

    def paged_summary(self) -> dict | None:
        """Page-pool occupancy, fragmentation, CoW sharing, and per-request
        block-count telemetry (None for the slot engine) — the paged
        analogue of :meth:`spec_summary`, surfaced by fleet reports."""
        if not self.paged:
            return None
        bm = self.block_manager
        tokens = 0
        blocks = []
        for s, r in enumerate(self.active):
            if r is None:
                continue
            st = self._admitting.get(s)
            tokens += st["pos"] if st is not None else int(self._len_host[s])
            blocks.append(len(self._pages[s]))
        out = {
            "page_size": self.page_size,
            **bm.utilization(tokens),
            **bm.stats,
            "preemptions": self.stats["preemptions"],
            "admit_skips": self.stats["admit_skips"],
            "active_requests": len(blocks),
            "blocks_per_request_max": max(blocks, default=0),
            "blocks_per_request_mean": (
                round(sum(blocks) / len(blocks), 3) if blocks else 0.0),
        }
        if self.prefix_cache is not None:
            out["prefix"] = self.prefix_cache.report()
        return out

    def _tok_out(self, tok: jax.Array):
        t = _fetch(tok)
        self.stats["host_syncs_decode"] += 1
        return tuple(int(x) for x in t) if t.ndim else int(t)

    def _retire(self, slot: int, *, reset_device: bool = False) -> None:
        req = self.active[slot]
        assert req is not None
        decode_s = time.perf_counter() - self._admit_s[slot]
        self.results[req.request_id] = RequestResult(
            request_id=req.request_id,
            tokens=self.generated[slot],
            decode_steps=len(self.generated[slot]),
            ttft_s=self._slot_ttft[slot],
            decode_s=decode_s,
        )
        self.active[slot] = None
        self.generated[slot] = []
        if self.spec is not None:
            self._hist[slot] = None
            self.proposer.retire(slot)
        if reset_device:  # fused path already zeroed these on device
            self.ctrl = dict(
                self.ctrl,
                lengths=self.ctrl["lengths"].at[slot].set(0),
                active=self.ctrl["active"].at[slot].set(False),
            )
        if self._slot_pins[slot] is not None:
            self.prefix_cache.release(self._slot_pins[slot])
            self._slot_pins[slot] = None
        if self.paged:
            self.block_manager.decref(self._pages[slot])
            self._pages[slot] = []
            self._bt_host[slot, :] = 0
            self._bt_dirty = True
        self.stats["retired"] += 1

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: admit, run one fused decode program for all
        B slots, sync the packed result (every ``sync_every`` steps), retire
        finished. Returns number of host-visible active slots."""
        with self._bound(), _span("serve.step"):
            return self._step_bound()

    def _step_bound(self) -> int:
        with _span("serve.admit"):
            self._admit()
        if not any(r is not None for r in self.active):
            self._flush()
            return 0
        if self.paged:
            # one chunk of every mid-prefill prompt, INTERLEAVED with the
            # decode step below — chunked prefill never stalls decodes
            self._prefill_step_paged()
            if self.role == "prefill":
                # prefill-only engines never decode: finished prompts left
                # as handoff packets above, mid-prefill rows continue next
                # step
                return sum(r is not None for r in self.active)
        if self.spec is not None:
            with _span("serve.decode"):
                self._step_spec()
        elif self.paged:
            self._step_fused_paged()
        elif self.fused:
            rows = sum(r is not None for r in self.active)
            with _span("serve.decode", rows=rows):
                self.rng, self.states, self.ctrl, packed = self._fused_step(
                    self.params, self.rng, self.states, self.ctrl)
            self.stats["decode_steps"] += 1
            self.stats["decode_rows"] += rows
            self._pending.append(packed)
            # flush at the window boundary — or early, when every in-flight
            # request has provably hit its token budget (each active slot
            # emits one token per buffered step unless it finished even
            # sooner), so the engine never burns whole-batch decode steps on
            # a drained batch just to reach the window edge
            if len(self._pending) >= self.sync_every or all(
                len(self.generated[i]) + len(self._pending) >= r.max_new_tokens
                for i, r in enumerate(self.active) if r is not None
            ):
                self._flush()
        else:
            with _span("serve.decode"):
                self._step_host()
        return sum(r is not None for r in self.active)

    def _step_spec(self) -> None:
        """One speculative engine iteration: the proposer drafts up to K
        tokens per active slot on the control plane, ONE fused program
        verifies all K+1 positions per slot (lossless rejection sampling
        inside the jit), and the host syncs a single packed matrix carrying
        up to K+1 emitted tokens per slot. Every step syncs — the proposer
        needs the emitted history — so the speedup is tokens-per-step, not
        syncs-per-step."""
        k = self.spec.k
        c = k + 1
        drafts = np.zeros((self.slots, k), np.int32)
        ndraft = np.zeros((self.slots,), np.int32)
        self.proposer.propose(self, drafts, ndraft)
        for i, r in enumerate(self.active):
            if r is None or i in self._admitting:
                # mid-prefill paged rows are ctrl-inactive: nothing to draft
                ndraft[i] = 0
                continue
            # never draft past the cache: position L+1+ndraft must stay
            # writable or the verify chunk's in-flight attention would read
            # dropped entries; never draft past the token budget either —
            # the step emits at most `remaining` tokens, so later drafts
            # could only be verified and thrown away
            room = self.max_len - int(self._len_host[i]) - 1
            remaining = r.max_new_tokens - len(self.generated[i])
            ndraft[i] = max(0, min(int(ndraft[i]), room, remaining - 1))
        if self.paged:
            for i, r in enumerate(self.active):
                if r is None or i in self._admitting:
                    continue
                length = int(self._len_host[i])
                hi = min(length + 1 + int(ndraft[i]), self.max_len)
                if not self._prepare_write(i, length, hi):
                    ndraft[i] = 0  # slot self-preempted under pool pressure
            self.rng, self.states, self.ctrl, packed = self._spec_step(
                self.params, self.rng, self.states, self.ctrl,
                jnp.asarray(drafts), jnp.asarray(ndraft), self._bt_device())
        else:
            self.rng, self.states, self.ctrl, packed = self._spec_step(
                self.params, self.rng, self.states, self.ctrl,
                jnp.asarray(drafts), jnp.asarray(ndraft))
        self.stats["decode_steps"] += 1
        self.stats["spec_steps"] += 1
        arr = np.asarray(_fetch(packed))
        self.stats["host_syncs_decode"] += 1
        for i in range(self.slots):
            if not arr[i, c + 1]:  # slot inactive at this step
                continue
            req = self.active[i]
            if req is None:
                continue
            m = int(arr[i, c])
            toks = [int(t) for t in arr[i, :m]]
            self.generated[i].extend(toks)
            self._hist[i] = np.concatenate(
                [self._hist[i], np.asarray(toks, np.int32)])
            self._len_host[i] += m
            self._last_host[i] = toks[-1]
            self.stats["decode_rows"] += 1
            self.stats["spec_positions"] += c
            self.stats["spec_drafted"] += int(ndraft[i])
            self.stats["spec_accepted"] += max(m - 1, 0)
            self.stats["spec_emitted"] += m
            if arr[i, c + 2]:
                self._retire(i)

    def spec_summary(self) -> dict | None:
        """Acceptance-rate telemetry for operators / fleet reports."""
        if self.spec is None:
            return None
        d, a = self.stats["spec_drafted"], self.stats["spec_accepted"]
        return {
            "proposer": self.proposer.kind,
            "k": self.spec.k,
            "steps": self.stats["spec_steps"],
            "drafted": d,
            "accepted": a,
            "acceptance_rate": round(a / max(d, 1), 4),
            "tokens_per_slot_step": round(
                self.stats["spec_emitted"]
                / max(self.stats["decode_rows"], 1), 4),
        }

    def latency_summary(self) -> dict:
        """p50/p95 TTFT and per-output-token decode latency (TPOT) over the
        completed requests, in real wall-clock seconds."""
        ttfts = [r.ttft_s for r in self.results.values()]
        tpots = [r.tpot_s for r in self.results.values() if len(r.tokens) > 1]
        pct = lambda xs, q: float(np.percentile(xs, q)) if xs else 0.0
        return {
            "requests": len(self.results),
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p95_s": pct(ttfts, 95),
            "tpot_p50_s": pct(tpots, 50),
            "tpot_p95_s": pct(tpots, 95),
        }

    def _flush(self) -> None:
        """Fetch all buffered packed step results in ONE blocking transfer
        and replay them through the host-side slot table."""
        if not self._pending:
            return
        with _span("serve.flush", steps=len(self._pending)):
            rows = _fetch(self._pending)
            self._pending = []
            self.stats["host_syncs_decode"] += 1
            audio = self.cfg.frontend == "audio"
            for arr in rows:  # (B, T+2): tokens..., active, done
                arr = np.asarray(arr)
                for i in range(self.slots):
                    if not arr[i, -2]:  # slot inactive at that step
                        continue
                    req = self.active[i]
                    if req is None:
                        continue
                    tok = arr[i, :-2]
                    self.generated[i].append(
                        tuple(int(x) for x in tok) if audio else int(tok[0]))
                    if arr[i, -1]:
                        self._retire(i)

    def _step_host(self) -> None:
        """Legacy per-slot host loop (the seed data plane): B scalar sample
        programs + one device_get per token + one length sync per slot."""
        self.ctrl["lengths"] = self.ctrl["lengths"] + jnp.asarray(
            [1 if r is not None else 0 for r in self.active], jnp.int32)
        logits, self.states = self._decode(
            self.params, self.ctrl["last"], self.states, self.ctrl["lengths"])
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += sum(r is not None for r in self.active)
        new_tokens = []
        for i in range(self.slots):
            req = self.active[i]
            if req is None:
                new_tokens.append(self._zero_tokens(1)[0])
                continue
            self.rng, k = jax.random.split(self.rng)
            tok = sample(k, logits[i], req.sampling)
            new_tokens.append(tok)
            self.generated[i].append(self._tok_out(tok))
            done = len(self.generated[i]) >= req.max_new_tokens
            if req.eos_id is not None and not done:
                t = self.generated[i][-1]
                done = (t == req.eos_id) if isinstance(t, int) else (t[0] == req.eos_id)
            length = int(self.ctrl["lengths"][i])
            self.stats["host_syncs_decode"] += 1
            if length >= self.max_len:
                done = True
            if done:
                self._retire(i, reset_device=True)
        self.ctrl["last"] = jnp.stack(new_tokens)

    def run_to_completion(self, max_steps: int = 10_000) -> dict[int, RequestResult]:
        """Drive the engine until every request completes or ``max_steps``
        engine iterations elapse. On truncation, ``stats['unserved']`` holds
        the count of requests left queued/in-flight (and a warning is
        logged) so callers can tell completion from truncation."""
        steps = 0
        while (self.queue or any(r is not None for r in self.active)) and steps < max_steps:
            self.step()
            steps += 1
        self._flush()
        unserved = len(self.queue) + sum(r is not None for r in self.active)
        self.stats["unserved"] = unserved
        if unserved:
            logger.warning(
                "run_to_completion hit max_steps=%d with %d request(s) unserved",
                max_steps, unserved)
        return self.results
