"""Disaggregated prefill/decode fleet: phase-specialized replica pools with
KV page handoff.

The XaaS converged model wins by specializing execution per workload phase
while keeping one lease/container abstraction (PAPER.md's Invocation
principle; rFaaS leases are the pool-allocation primitive). A monolithic
serving replica interleaves two phases with opposite resource shapes:

  * **prefill** is compute-bound and bursty — one long prompt occupies a
    slot for many chunked-prefill ticks, and every tick it runs starves the
    co-resident decode batch;
  * **decode** is memory-bound and steady — one token per slot per tick,
    latency set by KV residency, not FLOPs.

This module splits the fleet into a prefill-specialized pool (chunk cap =
``max_len``: a prompt admits in as few ticks as the bucket allows, because
there is no co-resident decode to protect) and a decode-specialized pool
(admits requests by *installing* already-computed KV pages — never runs a
prompt it can avoid), connected by a :class:`KVHandoff` transfer plane:

  prefill replica                        decode replica
  ─────────────────                      ─────────────────
  chunked prefill (full-width)           continuous decode batch
  first token = argmax(prefill logits)   ...
  export_pages -> gather -> host         |
      HandoffPacket {pages, shas} ──────>│ verify shas
      (virtual link: nbytes/bw + lat)    │ install_pages -> scatter
  decref on install ack <────────────────│ admit slot mid-decode

TTFT is charged at prefill completion (the first token is host-visible the
tick the prompt finishes — the handoff delays the *second* token, not the
first), which is exactly why the split wins: TTFT p99 under a prefill-heavy
burst no longer queues behind decode, and decode TPOT no longer stalls
behind prompt chunks. Fallback preserves liveness and byte parity: when the
prefill pool is empty or the handoff link backlogs past a watermark, new
requests are colocated monolithically on the decode pool (which keeps full
prefill capability), and a sha-mismatched transfer is dropped and recomputed
monolithically rather than trusted.

The autoscaler sizes the two pools independently — prefill against a TTFT
SLO, decode against a TPOT SLO — with per-pool cooldown/window state
(:mod:`repro.fleet.autoscaler`) and per-pool boot-cost awareness: each
pool's containers carry a role-keyed AOT bundle in the shared artifact
store, so a decode replica never compiles (or even loads) prefill-only
programs and vice versa.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Sequence

import numpy as np

from repro.core import recompile, scheduler
from repro.core.invocation import InvocationService
from repro.fleet.autoscaler import SLO, Autoscaler
from repro.fleet.manager import (BatchWorkload, FleetConfig, FleetManager,
                                 Replica, ReplicaState)
from repro.fleet.router import FleetRequest, Router
from repro.serving.engine import HandoffPacket, Request

__all__ = ["DisaggConfig", "HandoffTicket", "KVHandoff", "DisaggFleetManager"]


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """Pool sizing, handoff link model, and per-pool SLOs."""

    prefill_min: int = 1
    prefill_max: int = 2
    decode_min: int = 1
    decode_max: int = 2
    # per-pool engine geometry overrides (None = inherit FleetConfig)
    prefill_slots: int | None = None
    decode_slots: int | None = None
    # prefill pool chunk cap (None = max_len: admit in as few ticks as the
    # bucket ladder allows — there is no co-resident decode to protect)
    prefill_chunk_tokens: int | None = None
    # virtual handoff link: one serialized device->host->device staged copy
    # at a time, nbytes / bandwidth + latency per transfer
    handoff_bandwidth_bytes_per_s: float = 8 * (1 << 30)
    handoff_latency_s: float = 0.005
    # submit-time fallback trigger: pending+ready transfers above this
    # colocate new requests on the decode pool instead
    handoff_backlog_watermark: int = 8
    # per-pool SLOs: prefill pool defends TTFT, decode pool defends TPOT
    prefill_slo: SLO = dataclasses.field(default_factory=lambda: SLO(
        p95_target_s=1.0, queue_high_per_slot=1.0))
    decode_slo: SLO = dataclasses.field(default_factory=lambda: SLO(
        p95_target_s=0.12, queue_high_per_slot=2.0))


@dataclasses.dataclass
class HandoffTicket:
    """One KV page transfer in flight on the virtual link."""

    packet: HandoffPacket
    src: Replica
    submitted_s: float
    ready_s: float
    retries: int = 0


class KVHandoff:
    """Virtual-time KV page transfer plane between replica pools.

    Models one serialized staging link (device->host on the source, wire,
    host->device on the destination): each transfer occupies the link for
    ``nbytes / bandwidth`` and lands ``latency_s`` later. Integrity and
    lifetime are the *engines'* contract (`export_pages` pins the source
    pages, per-page shas travel with the payload, install verifies before
    scatter, the manager decrefs the source only after a successful
    install); this class only sequences time and backlog.
    """

    def __init__(self, *, bandwidth_bytes_per_s: float = 8 * (1 << 30),
                 latency_s: float = 0.005):
        self.bandwidth = float(bandwidth_bytes_per_s)
        self.latency_s = float(latency_s)
        self._pending: deque[HandoffTicket] = deque()   # in transfer order
        self._ready: deque[HandoffTicket] = deque()     # landed, not installed
        self._link_free_s = 0.0
        self.stats = {"submitted": 0, "delivered": 0, "installed": 0,
                      "sha_rejected": 0, "recomputed": 0, "retries": 0,
                      "bytes": 0, "transfer_s": 0.0, "wait_s": 0.0,
                      "max_backlog": 0}

    @property
    def backlog(self) -> int:
        return len(self._pending) + len(self._ready)

    def submit(self, now: float, packet: HandoffPacket, src: Replica) -> HandoffTicket:
        xfer = packet.nbytes / max(self.bandwidth, 1.0) + self.latency_s
        ready = max(now, self._link_free_s) + xfer
        self._link_free_s = ready
        t = HandoffTicket(packet=packet, src=src, submitted_s=now, ready_s=ready)
        self._pending.append(t)
        self.stats["submitted"] += 1
        self.stats["bytes"] += packet.nbytes
        self.stats["transfer_s"] += xfer
        self.stats["max_backlog"] = max(self.stats["max_backlog"], self.backlog)
        return t

    def take_ready(self, now: float) -> list[HandoffTicket]:
        """Move landed transfers to the ready set and return it (caller
        installs what it can and requeues the rest)."""
        while self._pending and self._pending[0].ready_s <= now:
            t = self._pending.popleft()
            self.stats["delivered"] += 1
            self.stats["wait_s"] += now - t.submitted_s
            self._ready.append(t)
        out = list(self._ready)
        self._ready.clear()
        return out

    def requeue(self, tickets: Sequence[HandoffTicket]) -> None:
        for t in tickets:
            t.retries += 1
            self.stats["retries"] += 1
            self._ready.append(t)


class DisaggFleetManager(FleetManager):
    """FleetManager with phase-specialized pools and a KV handoff plane.

    The base class owns leases, ticks, metering, harvest, and reporting;
    this subclass overrides placement (``submit``), the inter-pool data
    plane (``_post_step``), per-pool SLO feedback (``_record_completion``),
    and per-pool elasticity (``_autoscale`` / ``_boot_initial``).
    """

    def __init__(self, service: InvocationService, prefill_container,
                 decode_container, profile, *,
                 config: FleetConfig | None = None,
                 disagg: DisaggConfig | None = None,
                 autoscaler: Autoscaler | None = None,
                 router: Router | None = None,
                 batch: BatchWorkload | None = None):
        self.dcfg = disagg or DisaggConfig()
        d = self.dcfg
        super().__init__(service, decode_container, profile, config=config,
                         autoscaler=autoscaler or Autoscaler(
                             SLO(), d.prefill_min + d.decode_min,
                             d.prefill_max + d.decode_max),
                         router=router, batch=batch)
        # base `settled` logic compares against autoscaler.min_replicas
        self.autoscaler.min_replicas = d.prefill_min + d.decode_min
        self.autoscaler.max_replicas = d.prefill_max + d.decode_max
        self.prefill_container = prefill_container
        self.decode_container = decode_container
        self.handoff = KVHandoff(
            bandwidth_bytes_per_s=d.handoff_bandwidth_bytes_per_s,
            latency_s=d.handoff_latency_s)
        self._req_session: dict[int, str] = {}
        self.pool_counters = {"scale_ups_prefill": 0, "scale_ups_decode": 0,
                              "fallback_submits": 0}
        self._pool_peak = {"prefill": 0, "decode": 0}

    # ------------------------------------------------------------------
    def _container_for(self, pool: str | None):
        return (self.prefill_container if pool == "prefill"
                else self.decode_container)

    def _pool(self, pool: str, *states: ReplicaState) -> list[Replica]:
        states = states or (ReplicaState.BOOTING, ReplicaState.SERVING,
                            ReplicaState.DRAINING)
        return [r for r in self.replicas
                if r.pool == pool and r.state in states]

    def scale_up(self, now: float, *, initial: bool = False,
                 pool: str | None = None) -> Replica | None:
        r = super().scale_up(now, initial=initial, pool=pool)
        if r is not None and not initial and pool in ("prefill", "decode"):
            self.pool_counters[f"scale_ups_{pool}"] += 1
        return r

    def _boot_initial(self) -> None:
        for pool, n in (("decode", self.dcfg.decode_min),
                        ("prefill", self.dcfg.prefill_min)):
            while len(self._pool(pool, ReplicaState.BOOTING,
                                 ReplicaState.SERVING)) < n:
                if self.scale_up(0.0, initial=True, pool=pool) is None:
                    raise RuntimeError(
                        f"disagg fleet: cannot boot {pool} pool minimum "
                        f"({n}) — cluster too small even with BATCH "
                        "preemption")

    # ------------------------------------------------------------------
    # placement: new requests -> prefill pool; fallback -> colocate on decode
    # ------------------------------------------------------------------
    def submit(self, req: FleetRequest, now: float) -> Replica:
        self._req_tenant[req.request_id] = req.tenant
        self._arrival[req.request_id] = req.arrival_s
        self._req_session[req.request_id] = req.session
        prefill = [r for r in self.replicas if r.pool == "prefill"]
        colocate = (not any(r.accepting for r in prefill)
                    or self.handoff.backlog > self.dcfg.handoff_backlog_watermark)
        if colocate:
            # decode-role engines keep full prefill capability precisely for
            # this path: liveness (and byte parity) never depend on the
            # handoff plane being healthy
            self.pool_counters["fallback_submits"] += 1
            candidates = [r for r in self.replicas if r.pool == "decode"]
        else:
            candidates = prefill
        replica = self.router.route(req, candidates)
        replica.hot_buckets.add(replica.bucket_for(req.prompt_len))
        replica.executor.submit(Request(
            request_id=req.request_id, prompt=req.prompt,
            max_new_tokens=req.max_new_tokens, sampling=req.sampling))
        return replica

    # ------------------------------------------------------------------
    # the inter-pool data plane, pumped once per tick
    # ------------------------------------------------------------------
    def _post_step(self, now: float) -> None:
        t = now + self.cfg.tick_s
        # 1) collect finished prefill exports onto the virtual link. TTFT is
        # stamped HERE: the first token is host-visible the tick prefill
        # completes — the transfer delays the second token, not the first.
        for r in self.replicas:
            if r.pool != "prefill":
                continue
            out = getattr(r.engine, "handoff_out", None)
            while out:
                pkt = out.popleft()
                rid = pkt.request.request_id
                self._ttft_virtual.setdefault(rid, t - self._arrival[rid])
                self.handoff.submit(now, pkt, r)
        # 2) install landed transfers on the decode pool
        decode = [r for r in self._pool("decode", ReplicaState.SERVING)]
        retry = []
        for ticket in self.handoff.take_ready(now):
            pkt = ticket.packet
            rid = pkt.request.request_id
            session = self._req_session.get(rid, str(rid))
            target = self.router.route_handoff(session, pkt.prompt, decode)
            if target is None or not target.engine.can_install(pkt):
                retry.append(ticket)  # capacity: try again next tick
                continue
            if target.engine.install_handoff(pkt):
                # decref-on-source only after a VERIFIED install: the pin
                # taken by export_pages is the transfer's reference
                ticket.src.engine.release_handoff(pkt)
                self.handoff.stats["installed"] += 1
                target.hot_buckets.add(target.bucket_for(
                    int(np.asarray(pkt.prompt).shape[-1])))
            else:
                # sha mismatch: the payload is not the KV the source hashed.
                # Never trust it — drop the ticket, unpin the source pages,
                # and recompute the request monolithically on the decode pool
                ticket.src.engine.release_handoff(pkt)
                self.handoff.stats["sha_rejected"] += 1
                self._recompute(pkt, decode)
        self.handoff.requeue(retry)

    def _recompute(self, pkt: HandoffPacket, decode: list[Replica]) -> None:
        req = pkt.request
        fr = FleetRequest(
            request_id=req.request_id, tenant=self._tenant_of(req.request_id),
            session=self._req_session.get(req.request_id, str(req.request_id)),
            prompt=pkt.prompt, max_new_tokens=req.max_new_tokens,
            arrival_s=self._arrival.get(req.request_id, 0.0),
            sampling=req.sampling)
        replica = self.router.route(fr, decode or self.replicas)
        replica.executor.submit(Request(
            request_id=req.request_id, prompt=pkt.prompt,
            max_new_tokens=req.max_new_tokens, sampling=req.sampling))
        self.handoff.stats["recomputed"] += 1
        self.timeline.append(
            (self.now, f"handoff sha reject: request {req.request_id} "
                       f"recomputed on replica {replica.replica_id}"))

    # ------------------------------------------------------------------
    # per-pool SLO feedback + elasticity
    # ------------------------------------------------------------------
    def _record_completion(self, done_t: float, rid: int, res) -> None:
        lat = done_t - self._arrival[rid]
        ttft = self._ttft_virtual.get(rid, lat)
        self.autoscaler.record_completion(done_t, ttft, pool="prefill")
        n = self._req_tokens.get(rid, 1)
        if n > 1:
            tpot = max(lat - ttft, 0.0) / (n - 1)
            self.autoscaler.record_completion(done_t, tpot, pool="decode")

    def _autoscale(self, now: float) -> None:
        d = self.dcfg
        for pool, slo, lo, hi in (
                ("prefill", d.prefill_slo, d.prefill_min, d.prefill_max),
                ("decode", d.decode_slo, d.decode_min, d.decode_max)):
            serving = self._pool(pool, ReplicaState.SERVING)
            booting = self._pool(pool, ReplicaState.BOOTING)
            self._pool_peak[pool] = max(self._pool_peak[pool],
                                        len(serving) + len(booting))
            queued = sum(len(r.engine.queue) for r in self._pool(pool))
            if pool == "decode":
                # transfers in flight / awaiting install are decode-pool work
                # the queue can't see yet
                queued += self.handoff.backlog
            busy = sum(r.busy_slots() for r in serving)
            total = sum(r.engine.slots for r in serving + booting)
            action = self.autoscaler.decide(
                now, serving=len(serving), booting=len(booting),
                queued=queued, busy_slots=busy, total_slots=total,
                boot_cost_s=self._expected_boot_s(pool), pool=pool, slo=slo,
                min_replicas=lo, max_replicas=hi)
            if action == "up":
                self.scale_up(now, pool=pool)
            elif action == "down" and serving:
                victim = min(serving, key=lambda r: (r.outstanding_tokens(),
                                                     r.replica_id))
                self.drain(victim, now)

    # ------------------------------------------------------------------
    def shutdown(self) -> None:
        # drain the handoff plane in virtual time first: tickets only land
        # at their ready_s, and prefill replicas may still be exporting
        guard = 0
        while guard < 100_000 and (
                self.handoff.backlog
                or any(r.pool == "prefill" and r.has_work()
                       for r in self._by_state(ReplicaState.SERVING,
                                               ReplicaState.DRAINING))):
            self.now += self.cfg.tick_s
            self._step_replicas(self.now)
            self._post_step(self.now)
            self._stamp_ttft(self.now)
            self._harvest(self.now)
            guard += 1
        super().shutdown()

    def _disagg_summary(self) -> dict:
        d = self.dcfg
        pools = {}
        for pool, lo, hi in (("prefill", d.prefill_min, d.prefill_max),
                             ("decode", d.decode_min, d.decode_max)):
            live = self._pool(pool, ReplicaState.BOOTING, ReplicaState.SERVING)
            pools[pool] = {
                "min": lo, "max": hi,
                "live": len(live),
                "peak": self._pool_peak[pool],
                "ever": sum(r.pool == pool for r in self.replicas),
                "scale_ups": self.pool_counters[f"scale_ups_{pool}"],
            }
        return {
            "enabled": True,
            "handoff": {**self.handoff.stats, "backlog": self.handoff.backlog},
            "fallback_submits": self.pool_counters["fallback_submits"],
            "pools": pools,
        }

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, cfg, params, *, chips: int,
              fleet: FleetConfig | None = None,
              disagg: DisaggConfig | None = None,
              profile: recompile.SystemProfile | None = None,
              batch_jobs: Sequence[tuple[int, int]] = (),
              batch_step_s: float = 1.0, batch_ckpt_every: int = 5,
              store_factory=None) -> "DisaggFleetManager":
        """Assemble a disaggregated fleet on a fresh cluster: one
        role-specialized container per pool (distinct names, shared artifact
        store — the pools share one compiled-program corpus but each boots
        only its own role-keyed bundle)."""
        from repro.serving.service import serving_container

        fleet = fleet or FleetConfig()
        disagg = disagg or DisaggConfig()
        if fleet.page_size is None:
            raise ValueError("disaggregation requires paged KV: set "
                             "FleetConfig.page_size (and kv_pages)")
        profile = profile or recompile.detect_profile()
        service = InvocationService(scheduler.Cluster(chips=chips))
        spec = None
        if fleet.spec_k > 0:
            from repro.serving.speculative import SpecConfig
            spec = SpecConfig(k=fleet.spec_k, proposer=fleet.spec_proposer,
                              draft_arch=fleet.spec_draft_arch)
        common = dict(
            prompt_buckets=fleet.prompt_buckets, sync_every=fleet.sync_every,
            prefix_cache_bytes=int(fleet.prefix_cache_mb * (1 << 20)) or None,
            page_size=fleet.page_size, kv_pages=fleet.kv_pages,
            kv_watermark=fleet.kv_watermark, max_len=fleet.max_len,
            artifact_store=fleet.artifact_store)
        pre_cont = serving_container(
            cfg, params, slots=disagg.prefill_slots or fleet.slots,
            role="prefill", spec=None,
            prefill_chunk_tokens=(disagg.prefill_chunk_tokens
                                  or fleet.max_len),
            **common)
        dec_cont = serving_container(
            cfg, params, slots=disagg.decode_slots or fleet.slots,
            role="decode", spec=spec,
            prefill_chunk_tokens=fleet.prefill_chunk_tokens,
            **common)
        batch = None
        if batch_jobs:
            batch = BatchWorkload(service.cluster, step_s=batch_step_s,
                                  ckpt_every=batch_ckpt_every,
                                  store_factory=store_factory)
            for bchips, bsteps in batch_jobs:
                batch.submit(chips=bchips, total_steps=bsteps)
            service.cluster.run(until=service.cluster.now)
        return cls(service, pre_cont, dec_cont, profile, config=fleet,
                   disagg=disagg, batch=batch)
