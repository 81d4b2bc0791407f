"""Fleet control plane: N replica serving engines behind SERVICE leases,
SLO-driven elasticity, and interactive/batch coexistence via preemption.

This is the cluster-level layer the paper's allocation-model principle asks
for: long-running, performance-sensitive serving gets FaaS-style elasticity
("the flexibility and efficient resource utilization of serverless") without
giving up the leased, warm, compiled-data-plane execution model.

  * :class:`FleetManager` owns the replicas. Each replica is a
    ``ServingEngine`` booted behind its **own SERVICE lease** from
    ``InvocationService`` — so the warm-deployment cache (compiled decode
    artifact) and the engine program cache (jitted data-plane bundle) are
    shared across replicas, and every replica surfaces its specialization
    manifest at boot.
  * Requests are placed by the affinity :class:`~repro.fleet.router.Router`;
    completions feed the :class:`~repro.fleet.autoscaler.Autoscaler`, whose
    "up" decisions acquire a new lease (preempting BATCH training jobs
    through ``Cluster.preempt`` when the cluster is full — each preemption
    checkpoints through ``FTManager`` and requeues) and whose "down"
    decisions drain a replica and **release** its lease back to the free
    pool (scale-to-min).
  * Time is virtual: the fleet advances in ``tick_s`` steps, each tick
    running ONE real fused decode program per replica with work. The same
    objects run live under ``launch/serve.py --fleet``; latency, chip-second
    and utilization numbers come from the scheduler's virtual clock, so runs
    are deterministic given a trace seed.
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
import logging
import time
from typing import Any, Sequence

import numpy as np

from repro.core import recompile, scheduler
from repro.core.invocation import InvocationService, ServingExecutor
from repro.distributed import sharding as shd
from repro.fleet.autoscaler import SLO, Autoscaler, choose_replica_width
from repro.fleet.router import FleetRequest, Router
from repro.ft.manager import FTManager
from repro.serving.engine import Request, _bucket

__all__ = ["FleetConfig", "Replica", "ReplicaState", "BatchWorkload",
           "FleetManager", "FleetReport"]

logger = logging.getLogger(__name__)


class ReplicaState(enum.Enum):
    BOOTING = "booting"      # lease held, engine warming; accepts (queues) traffic
    SERVING = "serving"      # in rotation
    DRAINING = "draining"    # finishes in-flight + queued work, admits nothing
    RELEASED = "released"    # lease released, chips back in the free pool


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    tenant: str = "fleet-op"      # the lease holder: pays for chips
    min_replicas: int = 1
    max_replicas: int = 4
    # per-replica engine geometry
    slots: int = 2
    max_len: int = 96
    prompt_buckets: tuple[int, ...] = (16, 32, 48)
    sync_every: int = 1
    # radix prefix-cache byte budget per replica (0 disables KV reuse)
    prefix_cache_mb: float = 16.0
    # speculative decoding per replica (0 disables): every engine drafts
    # spec_k tokens per step and verifies them in one fused program with
    # lossless rejection sampling; acceptance telemetry lands per replica
    # in the fleet report
    spec_k: int = 0
    spec_proposer: str = "ngram"   # "ngram" | "draft"
    spec_draft_arch: str | None = None
    # paged KV per replica (None keeps contiguous per-slot KV strips): page
    # granularity in tokens, pool size in pages (None = full provisioning),
    # and the free-page watermark fraction admission respects
    page_size: int | None = None
    kv_pages: int | None = None
    kv_watermark: float = 0.05
    prefill_chunk_tokens: int | None = None
    # per-replica mesh geometry (None = single-chip replicas, the floor).
    # A (1, 2) mesh makes every replica a 2-chip tensor/expert-parallel
    # engine behind a 2-chip SERVICE lease: params + KV pools sharded by
    # the logical-axis rules, and the lease metered across ALL its chips.
    mesh_shape: tuple[int, ...] | None = None
    # candidate widths for the width-vs-count policy: when set, build()
    # calls autoscaler.choose_replica_width over these options under the
    # cluster's chip budget and records the chosen point in the timeline
    # (docs/sharding.md#replica-width-vs-replica-count)
    mesh_options: tuple[tuple[int, ...], ...] | None = None
    # virtual-time knobs
    tick_s: float = 0.05          # one fused decode round per replica per tick
    warm_boot_s: float = 0.5      # in-process program bundle already compiled
    cold_boot_s: float = 2.0      # first deploy: trace+compile the data plane
    ir_boot_s: float = 0.15       # IR-boot: deserialize persisted executables
    meter_every_s: float = 2.0    # ledger flush cadence
    settle_s: float = 40.0        # sim horizon past the last arrival
    # persistent AOT artifact store (checkpoint.store.ArtifactStore or
    # None): carried into the serving container so every replica boots
    # through the IR-boot ladder and cold compiles persist for the next
    # process (docs/ir-containers.md)
    artifact_store: Any = None


def replica_bytes_per_chip(cfg, fleet: "FleetConfig",
                           mesh_shape: tuple[int, ...]) -> int:
    """Modeled per-chip device bytes of ONE replica at the given width:
    params + the full KV pool (paged or contiguous, at this fleet's
    geometry), each leaf divided by the product of the mesh axes its
    logical-axis spec actually lands on. Pure shape arithmetic — abstract
    mesh, ``eval_shape`` trees, nothing materialized — so the width policy
    can be consulted before any engine exists (and for widths the local
    host cannot even build)."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    dt = jnp.dtype(cfg.activ_dtype)
    params = jax.eval_shape(
        lambda: transformer.init_model(jax.random.key(0), cfg))
    if fleet.page_size:
        kv_pages = fleet.kv_pages or (
            fleet.slots * (fleet.max_len // fleet.page_size) + 1)
        states = jax.eval_shape(lambda: transformer.init_paged_states(
            cfg, kv_pages, fleet.page_size, dt))
    else:
        states = jax.eval_shape(lambda: transformer.init_states(
            cfg, fleet.slots, fleet.max_len, dt))
    axes = (("data", "model")[-len(mesh_shape):] if len(mesh_shape) <= 2
            else ("pod", "data", "model")[-len(mesh_shape):])
    # abstract mesh: guarded_spec only reads mesh.shape, so one repeated
    # real device stands in for the whole grid
    devs = np.array(
        jax.devices() * int(np.prod(mesh_shape)))[: int(np.prod(mesh_shape))]
    mesh = shd.make_mesh(mesh_shape, axes, devs)
    with shd.use_rules(dict(shd.RULES_2D), mesh):
        pspecs = shd.param_pspecs(params)
        sspecs = shd.state_pspecs(states)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def per_chip(leaf, spec) -> int:
        denom = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                denom *= sizes[a]
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        return nbytes // max(denom, 1)

    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    total = 0
    for tree, specs in ((params, pspecs), (states, sspecs)):
        leaves = jax.tree.leaves(tree)
        specl = jax.tree.leaves(specs, is_leaf=is_spec)
        total += sum(per_chip(l, s) for l, s in zip(leaves, specl))
    return total


class Replica:
    """One serving engine behind its own SERVICE lease."""

    def __init__(self, replica_id: int, executor: ServingExecutor, *,
                 boot_until_s: float, started_s: float, boot: str,
                 pool: str = "serve"):
        self.replica_id = replica_id
        self.executor = executor
        self.engine = executor.engine
        self.pool = pool          # "serve" | "prefill" | "decode"
        self.state = ReplicaState.BOOTING
        self.boot = boot          # predicted rung: "warm" | "ir" | "cold"
        self.boot_path: str | None = None   # rung warmup() actually took
        self.boot_cost_s = 0.0    # virtual boot latency charged at scale-up
        self.boot_wall_s = 0.0    # real wall-clock of warmup()
        self.boot_until_s = boot_until_s
        self.started_s = started_s
        self.released_s: float | None = None
        self.chips = executor.lease.job.granted_chips
        # the engine's actual mesh geometry (None for single-device): what
        # report() surfaces per replica next to chips, so "2 chips" is
        # visibly a (1,2) tensor-parallel grid and not two engines
        self.mesh = shd.mesh_geometry(getattr(executor.engine, "mesh", None))
        self.hot_buckets: set[int] = set()
        self.manifest: dict | None = None
        self.last_flush_s = started_s
        self.harvested = 0  # results already seen by FleetManager._harvest

    # ---- router protocol ----
    @property
    def accepting(self) -> bool:
        return self.state in (ReplicaState.BOOTING, ReplicaState.SERVING)

    def outstanding_tokens(self) -> int:
        """Queued + remaining in-flight decode tokens — the router's load
        signal. Prefill-only replicas never decode, so their load is the
        prompt tokens still to be prefilled instead."""
        eng = self.engine
        if getattr(eng, "role", "both") == "prefill":
            queued = sum(int(np.asarray(r.prompt).shape[-1]) for r in eng.queue)
            admitting = sum(st["plen"] - st["pos"]
                            for st in eng._admitting.values())
            return queued + admitting
        queued = sum(r.max_new_tokens for r in eng.queue)
        inflight = sum(
            max(r.max_new_tokens - len(eng.generated[i]), 0)
            for i, r in enumerate(eng.active) if r is not None)
        return queued + inflight

    def bucket_for(self, prompt_len: int) -> int:
        return _bucket(prompt_len, self.engine.prompt_buckets)

    def cached_prefix_len(self, prompt) -> int:
        """Longest usable cached prefix this replica advertises for the
        router's prefix-affinity layer (0 when the cache is disabled)."""
        cache = self.engine.prefix_cache
        if cache is None:
            return 0
        t = np.asarray(prompt)
        return cache.match(t, limit=t.shape[-1] - 1).usable

    # ---- manager internals ----
    def has_work(self) -> bool:
        eng = self.engine
        return bool(eng.queue) or any(r is not None for r in eng.active)

    def busy_slots(self) -> int:
        return sum(r is not None for r in self.engine.active)


@dataclasses.dataclass
class _BatchJob:
    job: scheduler.Job
    total_steps: int
    ft: FTManager
    progress: float = 0.0   # virtual training steps completed
    ckpt_step: int = 0      # last committed checkpoint


class BatchWorkload:
    """Preemptible BATCH training jobs sharing the cluster with the fleet.

    Each job's progress advances in virtual time; checkpoints go through the
    same ``FTManager`` save hook real training uses. The scheduler's graceful
    preemption window (``Cluster.preempt`` fires listeners *before* taking
    the chips) triggers a final checkpoint, and when a requeued job restarts,
    ``FTManager.resume`` restores progress from the last committed step — the
    paper's interactive/batch coexistence with no lost work.
    """

    def __init__(self, cluster: scheduler.Cluster, *, tenant: str = "train",
                 step_s: float = 1.0, ckpt_every: int = 5,
                 store_factory=None):
        """``store_factory(job_id) -> CheckpointStore`` makes checkpoints hit
        real storage; the default keeps them in memory (same FTManager code
        path, no disk)."""
        self.cluster = cluster
        self.tenant = tenant
        self.step_s = step_s
        self.ckpt_every = ckpt_every
        self._store_factory = store_factory
        self.jobs: dict[int, _BatchJob] = {}
        self.stats = {"submitted": 0, "checkpoints": 0, "preemptions": 0,
                      "resumes": 0}
        cluster.listeners.append(self._on_event)

    def submit(self, *, chips: int, total_steps: int) -> scheduler.Job:
        job = self.cluster.submit(
            tenant=self.tenant, chips=chips,
            runtime_s=total_steps * self.step_s,
            klass=scheduler.JobClass.BATCH)
        store = self._store_factory(job.job_id) if self._store_factory else None
        mem: dict[int, Any] = {}  # in-memory fallback: step -> state

        def save(state, step):
            if store is not None:
                store.save(int(step), {"data_step": np.asarray(state["data_step"])},
                           meta={"job": job.job_id}, blocking=True)
            else:
                mem[int(step)] = state

        def make_step(mesh_size):
            if store is not None:
                last = store.latest_step() or 0
            else:
                last = max(mem) if mem else 0
            return None, {"data_step": np.asarray(last)}, last

        ft = FTManager(make_step=make_step, save=save,
                       ckpt_every=self.ckpt_every, min_mesh=1)
        self.jobs[job.job_id] = _BatchJob(job=job, total_steps=total_steps, ft=ft)
        self.stats["submitted"] += 1
        return job

    def _on_event(self, kind: str, job: scheduler.Job) -> None:
        entry = self.jobs.get(job.job_id)
        if entry is None:
            return
        if kind == "preempt":
            # graceful window: chips still held — commit a final checkpoint
            step = int(entry.progress)
            entry.ckpt_step = entry.ft.checkpoint(
                {"data_step": np.asarray(step)}, step)
            self.stats["checkpoints"] += 1
            self.stats["preemptions"] += 1
            logger.info("batch job %d preempted at step %d (checkpointed)",
                        job.job_id, step)
        elif kind == "start" and job.preemptions > 0:
            # requeued job restarting: restore from the committed checkpoint
            _, state, step = entry.ft.resume(job.granted_chips)
            entry.progress = float(step)
            self.stats["resumes"] += 1
            logger.info("batch job %d resumed from checkpoint step %d",
                        job.job_id, step)

    def tick(self, now: float, dt: float) -> None:
        for entry in self.jobs.values():
            if entry.job.state != scheduler.JobState.RUNNING:
                continue
            entry.progress = min(entry.progress + dt / self.step_s,
                                 entry.total_steps)
            step = int(entry.progress)
            if step - entry.ckpt_step >= self.ckpt_every:
                entry.ft.save({"data_step": np.asarray(step)}, step)
                entry.ckpt_step = step
                self.stats["checkpoints"] += 1

    def summary(self) -> dict:
        return {
            **self.stats,
            "jobs": {
                jid: {"state": e.job.state.value, "preemptions": e.job.preemptions,
                      "progress_steps": round(e.progress, 2),
                      "total_steps": e.total_steps, "ckpt_step": e.ckpt_step}
                for jid, e in self.jobs.items()
            },
        }


@dataclasses.dataclass
class FleetReport:
    """Everything a benchmark or CI assertion needs from one fleet run."""

    requests: int
    served: int
    tokens: int
    duration_s: float
    latency_p50_s: float
    latency_p95_s: float
    latency_p99_s: float
    # real wall-clock engine-side latency telemetry (unlike the virtual-time
    # latencies above): TTFT = submit -> first token on the host, TPOT =
    # decode wall per output token after the first, aggregated over every
    # completed request across replicas
    ttft_p50_s: float
    ttft_p95_s: float
    tpot_p50_s: float
    tpot_p95_s: float
    tokens_per_s: float            # virtual-time throughput
    serving_chip_s: float          # chip-seconds held by SERVICE leases
    utilization: float             # cluster busy fraction (all job classes)
    scale_ups: int
    scale_downs: int
    lease_releases: int
    preemptions: int               # BATCH preemptions triggered by scale-up
    tokens_by_tenant: dict[str, int]
    metered_by_tenant: dict[str, int]
    reconciled: bool               # ledger totals match served tokens per tenant
    prefix_cache: dict             # fleet-wide prefix reuse + router affinity
    speculative: dict              # fleet-wide draft/accept telemetry
    paged_kv: dict                 # fleet-wide page-pool occupancy/CoW telemetry
    boot: dict                     # per-rung boot counts + latencies + the
                                   # expected cost of the next scale-up
    replicas: list[dict]
    batch: dict
    decisions: list[tuple[float, str, str]]
    # virtual-time TTFT (arrival -> first token tick): includes queueing
    # delay, which the wall-clock ttft_s above cannot see — the disagg
    # benchmark's headline metric
    ttft_virtual_p50_s: float = 0.0
    ttft_virtual_p95_s: float = 0.0
    ttft_virtual_p99_s: float = 0.0
    phase_metering: dict = dataclasses.field(default_factory=dict)
    disagg: dict = dataclasses.field(default_factory=dict)
    # the chosen point on the replica-width vs replica-count curve (empty
    # when the fleet runs fixed single-chip replicas): mesh shape, chips
    # per replica, and the policy's reason string
    width_decision: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["decisions"] = [[round(t, 3), a, r] for t, a, r in self.decisions]
        return d


class FleetManager:
    """Owns the replica set and runs the virtual-time serving loop."""

    def __init__(self, service: InvocationService, container, profile,
                 *, config: FleetConfig | None = None,
                 autoscaler: Autoscaler | None = None,
                 router: Router | None = None,
                 batch: BatchWorkload | None = None,
                 width_decision: dict | None = None):
        self.service = service
        self.cluster = service.cluster
        self.container = container
        self.profile = profile
        self.cfg = config or FleetConfig()
        self.width_decision = width_decision or {}
        self.autoscaler = autoscaler or Autoscaler(
            SLO(), self.cfg.min_replicas, self.cfg.max_replicas)
        self.router = router or Router()
        self.batch = batch
        self.replicas: list[Replica] = []
        self._rid = itertools.count()
        self._req_tenant: dict[int, str] = {}
        self._arrival: dict[int, float] = {}
        self._completion: dict[int, float] = {}
        self._req_tokens: dict[int, int] = {}
        # virtual-time TTFT: first tick at which a request had >= 1 token,
        # minus arrival. Complements the wall-clock ttft_s telemetry (which
        # measures host compute, not queueing) — queueing delay under load is
        # exactly what disaggregation improves, so benchmarks gate on this.
        self._ttft_virtual: dict[int, float] = {}
        self.counters = {"scale_ups": 0, "scale_downs": 0, "lease_releases": 0,
                         "preempts_triggered": 0, "scale_up_failures": 0}
        self.timeline: list[tuple[float, str]] = []
        if self.width_decision:
            self.timeline.append(
                (0.0, f"width decision: {self.width_decision['reason']}"))
        self.now = 0.0
        self._last_meter = 0.0

    # ------------------------------------------------------------------
    def _by_state(self, *states: ReplicaState) -> list[Replica]:
        return [r for r in self.replicas if r.state in states]

    def _tenant_of(self, request_id: int) -> str:
        return self._req_tenant.get(request_id, self.cfg.tenant)

    # ------------------------------------------------------------------
    # elasticity actions
    # ------------------------------------------------------------------
    def _container_for(self, pool: str | None):
        """Container a new replica in ``pool`` deploys. The monolithic fleet
        has one container; disaggregated subclasses map pool -> role-
        specialized container (distinct names, so warm-deployment caching
        never aliases a prefill bundle into a decode replica)."""
        return self.container

    def scale_up(self, now: float, *, initial: bool = False,
                 pool: str | None = None) -> Replica | None:
        """Acquire one more SERVICE lease and boot a replica behind it. When
        the cluster is full, RUNNING BATCH jobs are preempted (youngest
        first: least progress to requeue) until the lease's job starts; if
        even preemption can't free enough chips, the lease is released and
        the attempt recorded as a failure. ``initial`` marks the
        min-footprint boots at fleet start, which are NOT counted as elastic
        scale-ups (otherwise the 'did the autoscaler act' assertions in the
        benchmark/CI would be vacuously true)."""
        warm_before = self.service.stats["warm_acquires"]
        ex = self.service.acquire_serving(
            self.cfg.tenant, self._container_for(pool), self.profile,
            tenant_of=self._tenant_of, pool=pool or "serve")
        job = ex.lease.job
        if job.state != scheduler.JobState.RUNNING:
            victims = sorted(
                (self.cluster.jobs[i] for i in self.cluster.running
                 if self.cluster.jobs[i].klass == scheduler.JobClass.BATCH),
                key=lambda j: -(j.start_s or 0.0))  # youngest first
            for victim in victims:
                self.cluster.preempt(victim.job_id)
                self.cluster.run(until=self.cluster.now)
                self.counters["preempts_triggered"] += 1
                self.timeline.append(
                    (now, f"preempt batch job {victim.job_id} for scale-up"))
                if job.state == scheduler.JobState.RUNNING:
                    break
        if job.state != scheduler.JobState.RUNNING:
            ex.release()
            self.counters["scale_up_failures"] += 1
            self.timeline.append((now, "scale-up failed: no preemptible capacity"))
            return None
        # predicted boot rung, from MODELED state only: "warm" when THIS
        # fleet's deployment cache hit (a previous replica already deployed
        # the same container), else the engine's persisted-IR-vs-cold
        # preview. The engine's warm rung is deliberately not consulted —
        # the in-process program bundle can be hot for reasons outside this
        # fleet's virtual history (another fleet run earlier in the same
        # process), and virtual boot cost must stay hermetic per manager.
        # warmup() still takes the cheapest REAL rung; r.boot_path records
        # it separately.
        if self.service.stats["warm_acquires"] > warm_before:
            boot = "warm"
        else:
            preview = getattr(ex.engine, "boot_path_preview", None)
            boot = (preview(assume_fresh_process=True)
                    if preview is not None else "cold")
        boot_s = self._boot_cost_s(boot)
        replica = Replica(next(self._rid), ex, boot_until_s=now + boot_s,
                          started_s=now, boot=boot, pool=pool or "serve")
        replica.boot_cost_s = boot_s
        self.replicas.append(replica)
        if not initial:
            self.counters["scale_ups"] += 1
        ptag = f" [{pool}]" if pool else ""
        # the width half of every elasticity step is explicit in the
        # timeline: "added a replica (1 chip)" vs "added a widened replica
        # (mesh 1x2, 2 chips)" — a widened scale-up spends the chip budget
        # chips-per-replica at a time, the tradeoff the t=0 width decision
        # picked
        if replica.mesh is not None and replica.chips > 1:
            geom = "x".join(str(d) for d in replica.mesh[0])
            wtag = f" widened replica (mesh {geom}, {replica.chips} chips):"
        else:
            wtag = " replica (1 chip):" if not initial else ": replica"
        verb = "boot" if initial else "scale-up: added"
        self.timeline.append(
            (now, f"{verb}{wtag} {replica.replica_id}{ptag} "
                  f"({boot} boot, lease {ex.lease.lease_id})"))
        return replica

    def drain(self, replica: Replica, now: float) -> None:
        """Take a replica out of rotation; its lease is released once the
        queue and in-flight slots empty."""
        replica.state = ReplicaState.DRAINING
        self.router.forget_replica(replica.replica_id)
        self.timeline.append((now, f"drain: replica {replica.replica_id}"))

    def _release_drained(self, now: float) -> None:
        for r in self._by_state(ReplicaState.DRAINING):
            if r.has_work():
                continue
            # scale-to-min is the moment this replica's compiled corpus is
            # most complete (live traffic exercised shapes warmup's sweep
            # missed, e.g. spec_step_for(k) for k seen only under load) —
            # persist so the NEXT boot is a full IR hit
            if getattr(r.engine, "artifact_store", None) is not None:
                persisted = r.engine.persist_programs()
                self.timeline.append(
                    (now, f"persist: replica {r.replica_id} "
                          f"{persisted.get('persisted', 0)} executables"))
            r.executor.meter_flush(max(now - r.last_flush_s, 0.0))
            r.executor.release()  # asserts chips returned to the free pool
            r.state = ReplicaState.RELEASED
            r.released_s = now
            self.counters["scale_downs"] += 1
            self.counters["lease_releases"] += 1
            self.timeline.append(
                (now, f"release: replica {r.replica_id} lease "
                      f"{r.executor.lease.lease_id} (scale-to-min)"))

    # ------------------------------------------------------------------
    # per-tick phases
    # ------------------------------------------------------------------
    def submit(self, req: FleetRequest, now: float) -> Replica:
        self._req_tenant[req.request_id] = req.tenant
        self._arrival[req.request_id] = req.arrival_s
        replica = self.router.route(req, self.replicas)
        replica.hot_buckets.add(replica.bucket_for(req.prompt_len))
        replica.executor.submit(Request(
            request_id=req.request_id, prompt=req.prompt,
            max_new_tokens=req.max_new_tokens, sampling=req.sampling))
        return replica

    def _promote_boots(self, now: float) -> None:
        for r in self._by_state(ReplicaState.BOOTING):
            if now >= r.boot_until_s:
                t0 = time.perf_counter()
                r.manifest = r.executor.warmup()
                r.boot_wall_s = time.perf_counter() - t0
                boot = (r.manifest or {}).get("boot") or {}
                r.boot_path = boot.get("path", r.boot)
                r.state = ReplicaState.SERVING
                self.timeline.append(
                    (now, f"serving: replica {r.replica_id} "
                          f"({r.boot_path}-boot {r.boot_wall_s:.2f}s)"))

    def _boot_cost_s(self, path: str) -> float:
        return {"warm": self.cfg.warm_boot_s,
                "ir": self.cfg.ir_boot_s}.get(path, self.cfg.cold_boot_s)

    def _expected_boot_s(self, pool: str | None = None) -> float:
        """Virtual boot cost the NEXT scale-up would pay, from modeled
        fleet state: any live replica means this fleet's deployment cache
        is hot (warm boot); with none, a stocked artifact store IR-boots;
        otherwise cold. With ``pool`` given, only same-pool replicas
        answer (pool bundles are role-keyed, so a decode replica cannot
        vouch for a prefill boot)."""
        for r in self._by_state(ReplicaState.SERVING, ReplicaState.BOOTING,
                                ReplicaState.DRAINING):
            if pool is not None and r.pool != pool:
                continue
            return self.cfg.warm_boot_s
        store = self.cfg.artifact_store
        if store is not None and store.keys():
            return self.cfg.ir_boot_s
        return self.cfg.cold_boot_s

    def _step_replicas(self, now: float) -> None:
        for r in self._by_state(ReplicaState.SERVING, ReplicaState.DRAINING):
            if r.has_work():
                r.executor.step()

    def _harvest(self, now: float) -> None:
        done_t = now + self.cfg.tick_s
        for r in self.replicas:
            results = r.engine.results
            if len(results) == r.harvested:
                continue
            # results is insertion-ordered and retirement only appends, so
            # everything past the cursor is new — no full rescan per tick
            for rid, res in itertools.islice(results.items(), r.harvested, None):
                self._completion[rid] = done_t
                self._req_tokens[rid] = len(res.tokens)
                # single-tick requests retire before _stamp_ttft sees them
                self._ttft_virtual.setdefault(rid, done_t - self._arrival[rid])
                self._record_completion(done_t, rid, res)
            r.harvested = len(results)

    def _record_completion(self, done_t: float, rid: int, res) -> None:
        """Feed one completion into the autoscaler. The monolithic fleet
        records end-to-end latency into the default pool; disaggregated
        subclasses split the sample into per-pool SLO signals."""
        self.autoscaler.record_completion(done_t, done_t - self._arrival[rid])

    def _stamp_ttft(self, now: float) -> None:
        """Record virtual TTFT for any in-flight request whose first token
        landed this tick (the tick's results become visible at now+tick_s,
        matching ``_harvest``'s completion stamps)."""
        t = now + self.cfg.tick_s
        for r in self._by_state(ReplicaState.SERVING, ReplicaState.DRAINING):
            eng = r.engine
            for i, req in enumerate(eng.active):
                if req is None or not eng.generated[i]:
                    continue
                rid = req.request_id
                if rid not in self._ttft_virtual and rid in self._arrival:
                    self._ttft_virtual[rid] = t - self._arrival[rid]

    def _post_step(self, now: float) -> None:
        """Hook between replica stepping and harvest — the disaggregated
        fleet pumps KV handoffs (export -> transfer -> install) here."""

    def _autoscale(self, now: float) -> None:
        serving = self._by_state(ReplicaState.SERVING)
        booting = self._by_state(ReplicaState.BOOTING)
        queued = sum(len(r.engine.queue)
                     for r in self._by_state(ReplicaState.BOOTING,
                                             ReplicaState.SERVING,
                                             ReplicaState.DRAINING))
        busy = sum(r.busy_slots() for r in serving)
        # booting slots count toward queue capacity: a replica already on its
        # way up shouldn't trigger another scale-up for the same backlog
        total = sum(r.engine.slots for r in serving + booting)
        action = self.autoscaler.decide(
            now, serving=len(serving), booting=len(booting), queued=queued,
            busy_slots=busy, total_slots=total,
            boot_cost_s=self._expected_boot_s())
        if action == "up":
            self.scale_up(now)
        elif action == "down" and serving:
            victim = min(serving,
                         key=lambda r: (r.outstanding_tokens(), r.replica_id))
            self.drain(victim, now)

    def _meter_tick(self, now: float) -> None:
        if now - self._last_meter < self.cfg.meter_every_s:
            return
        self._last_meter = now
        for r in self._by_state(ReplicaState.BOOTING, ReplicaState.SERVING,
                                ReplicaState.DRAINING):
            r.executor.meter_flush(max(now - r.last_flush_s, 0.0))
            r.last_flush_s = now

    def _boot_initial(self) -> None:
        """Boot the fleet's minimum footprint at t=0 (not counted as elastic
        scale-ups). Disaggregated subclasses boot each pool to its own
        minimum."""
        while len(self._by_state(ReplicaState.BOOTING, ReplicaState.SERVING)) \
                < self.autoscaler.min_replicas:
            if self.scale_up(0.0, initial=True) is None:
                raise RuntimeError(
                    "fleet: cannot boot min_replicas — cluster too small even "
                    "with BATCH preemption")

    # ------------------------------------------------------------------
    def run_trace(self, requests: Sequence[FleetRequest], *,
                  until_s: float | None = None) -> FleetReport:
        """Drive the fleet through a trace in virtual time and return the
        report. By default runs until every request is served AND the fleet
        has settled back to ``min_replicas`` (so scale-to-min is part of
        every run). An explicit ``until_s`` is a hold-until horizon: the
        fleet keeps simulating (idle at min footprint) to exactly that time,
        which is what makes chip-second comparisons across allocation
        policies share one accounting window."""
        reqs = sorted(requests, key=lambda r: (r.arrival_s, r.request_id))
        explicit_horizon = until_s is not None
        horizon = until_s if explicit_horizon else (
            (reqs[-1].arrival_s if reqs else 0.0) + self.cfg.settle_s)
        self._boot_initial()
        i, t = 0, 0.0
        while True:
            while i < len(reqs) and reqs[i].arrival_s <= t:
                self.submit(reqs[i], t)
                i += 1
            self._promote_boots(t)
            self._step_replicas(t)
            self._post_step(t)
            self._stamp_ttft(t)
            self._harvest(t)
            self._autoscale(t)
            if self.batch is not None:
                self.batch.tick(t, self.cfg.tick_s)
            self._meter_tick(t)
            self._release_drained(t)
            self.cluster.advance_to(t)
            self.now = t
            done = i >= len(reqs) and len(self._completion) >= len(reqs)
            settled = (not self._by_state(ReplicaState.BOOTING,
                                          ReplicaState.DRAINING)
                       and len(self._by_state(ReplicaState.SERVING))
                       <= self.autoscaler.min_replicas)
            if explicit_horizon:
                if done and t >= horizon:
                    break
            elif done and (settled or t >= horizon):
                break
            if t >= horizon + 120.0:  # safety: never loop forever
                logger.warning("fleet: horizon safety stop at t=%.1f "
                               "(%d/%d served)", t, len(self._completion),
                               len(reqs))
                break
            t += self.cfg.tick_s
        for r in self._by_state(ReplicaState.BOOTING, ReplicaState.SERVING,
                                ReplicaState.DRAINING):
            r.executor.meter_flush(max(t - r.last_flush_s, 0.0))
            r.last_flush_s = t
        return self.report()

    def shutdown(self) -> None:
        """Release every remaining lease (end of the fleet's life); the
        warm deployment stays cached for the next fleet."""
        for r in self._by_state(ReplicaState.BOOTING, ReplicaState.SERVING):
            self.drain(r, self.now)
        guard = 0
        while self._by_state(ReplicaState.DRAINING) and guard < 100_000:
            self._step_replicas(self.now)
            self._harvest(self.now)
            self._release_drained(self.now)
            guard += 1

    # ------------------------------------------------------------------
    def report(self) -> FleetReport:
        lats = [self._completion[rid] - self._arrival[rid]
                for rid in self._completion]
        tokens_by_tenant: dict[str, int] = {}
        for rid, n in self._req_tokens.items():
            tenant = self._tenant_of(rid)
            tokens_by_tenant[tenant] = tokens_by_tenant.get(tenant, 0) + n
        metered = {tenant: self.service.meter.served_tokens(tenant)
                   for tenant in tokens_by_tenant}
        tokens = sum(self._req_tokens.values())
        reconciled = (metered == tokens_by_tenant
                      and self.service.meter.served_tokens() == tokens)
        self.cluster.check_invariants()
        self.service.meter.check_invariants()
        pct = (lambda q: float(np.percentile(lats, q)) if lats else 0.0)
        serving_chip_s = sum(
            ((r.released_s if r.released_s is not None else self.now)
             - r.started_s) * r.chips
            for r in self.replicas)

        def _replica_prefix(r: Replica) -> dict | None:
            eng = r.engine
            if eng.prefix_cache is None:
                return None
            h, m = eng.stats["prefix_hits"], eng.stats["prefix_misses"]
            return {
                "hits": h,
                "misses": m,
                "hit_rate": round(h / max(h + m, 1), 4),
                "hit_tokens": eng.stats["prefix_hit_tokens"],
                "prefill_tokens": eng.stats["prefill_tokens"],
                **{k: v for k, v in eng.prefix_cache.report().items()
                   if k in ("nodes", "bytes", "evictions", "inserts")},
            }

        per_replica_prefix = {r.replica_id: _replica_prefix(r)
                              for r in self.replicas}
        per_replica_spec = {r.replica_id: r.engine.spec_summary()
                            for r in self.replicas}
        sagg = [s for s in per_replica_spec.values() if s]
        drafted = sum(s["drafted"] for s in sagg)
        accepted = sum(s["accepted"] for s in sagg)
        spec_summary = {
            "enabled": bool(sagg),
            "drafted": drafted,
            "accepted": accepted,
            "acceptance_rate": round(accepted / max(drafted, 1), 4),
            "steps": sum(s["steps"] for s in sagg),
        }
        per_replica_paged = {r.replica_id: r.engine.paged_summary()
                             for r in self.replicas}
        pagg = [p for p in per_replica_paged.values() if p]
        paged_summary = {
            "enabled": bool(pagg),
            "pages_total": sum(p["pages_total"] for p in pagg),
            "pages_in_use": sum(p["pages_in_use"] for p in pagg),
            "peak_in_use": sum(p["peak_in_use"] for p in pagg),
            "cow_copies": sum(p["cow_copies"] for p in pagg),
            "cow_shared_pages": sum(p["cow_shared_pages"] for p in pagg),
            "preemptions": sum(p["preemptions"] for p in pagg),
            "admit_skips": sum(p["admit_skips"] for p in pagg),
        }
        ttfts, tpots = [], []
        for r in self.replicas:
            for res in r.engine.results.values():
                ttfts.append(res.ttft_s)
                if len(res.tokens) > 1:
                    tpots.append(res.tpot_s)
        tvs = [self._ttft_virtual[rid] for rid in self._completion
               if rid in self._ttft_virtual]
        rpct = (lambda xs, q: float(np.percentile(xs, q)) if xs else 0.0)
        agg = [p for p in per_replica_prefix.values() if p]
        hits = sum(p["hits"] for p in agg)
        misses = sum(p["misses"] for p in agg)
        prefix_summary = {
            "enabled": bool(agg),
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 4),
            "hit_tokens": sum(p["hit_tokens"] for p in agg),
            "prefill_tokens": sum(p["prefill_tokens"] for p in agg),
            "prefix_affinity_routes": self.router.stats.get("prefix_hits", 0),
            "session_affinity_routes": self.router.stats.get("session_hits", 0),
        }
        booted = [r for r in self.replicas if r.boot_path is not None]
        paths: dict[str, int] = {}
        wall_by_path: dict[str, float] = {}
        for r in booted:
            paths[r.boot_path] = paths.get(r.boot_path, 0) + 1
            wall_by_path[r.boot_path] = round(
                wall_by_path.get(r.boot_path, 0.0) + r.boot_wall_s, 6)
        boot_summary = {
            "paths": paths,
            "wall_s_by_path": wall_by_path,
            "virtual_boot_s": round(sum(r.boot_cost_s for r in booted), 6),
            "expected_next_boot_s": self._expected_boot_s(),
        }
        return FleetReport(
            requests=len(self._arrival),
            served=len(self._completion),
            tokens=tokens,
            duration_s=self.now,
            latency_p50_s=pct(50),
            latency_p95_s=pct(95),
            latency_p99_s=pct(99),
            ttft_p50_s=rpct(ttfts, 50),
            ttft_p95_s=rpct(ttfts, 95),
            tpot_p50_s=rpct(tpots, 50),
            tpot_p95_s=rpct(tpots, 95),
            tokens_per_s=tokens / max(self.now, 1e-9),
            serving_chip_s=serving_chip_s,
            utilization=self.cluster.utilization(),
            scale_ups=self.counters["scale_ups"],
            scale_downs=self.counters["scale_downs"],
            lease_releases=self.counters["lease_releases"],
            preemptions=self.counters["preempts_triggered"],
            tokens_by_tenant=tokens_by_tenant,
            metered_by_tenant=metered,
            reconciled=reconciled,
            prefix_cache=prefix_summary,
            speculative=spec_summary,
            paged_kv=paged_summary,
            boot=boot_summary,
            replicas=[{
                "id": r.replica_id,
                "chips": r.chips,
                "mesh": (None if r.mesh is None
                         else {"shape": list(r.mesh[0]),
                               "axes": list(r.mesh[1])}),
                "boot": r.boot,
                "boot_path": r.boot_path,
                "boot_s": round(r.boot_cost_s, 3),
                "boot_wall_s": round(r.boot_wall_s, 3),
                "start_s": round(r.started_s, 3),
                "end_s": (round(r.released_s, 3)
                          if r.released_s is not None else None),
                "state": r.state.value,
                "prefix": per_replica_prefix[r.replica_id],
                "spec": per_replica_spec[r.replica_id],
                "paged": per_replica_paged[r.replica_id],
                "tiers": ({api: c["provider"]
                           for api, c in r.manifest.get("apis", {}).items()}
                          if r.manifest else None),
            } for r in self.replicas],
            batch=self.batch.summary() if self.batch else {},
            decisions=list(self.autoscaler.decisions),
            ttft_virtual_p50_s=rpct(tvs, 50),
            ttft_virtual_p95_s=rpct(tvs, 95),
            ttft_virtual_p99_s=rpct(tvs, 99),
            phase_metering={
                "prefill_tokens": self.service.meter.total_steps("serve_prefill"),
                "decode_steps": self.service.meter.total_steps("serve_decode"),
                "spec_positions": self.service.meter.total_steps(
                    "serve_spec_verify"),
            },
            disagg=self._disagg_summary(),
            width_decision=dict(self.width_decision),
        )

    def _disagg_summary(self) -> dict:
        """Handoff/pool telemetry — empty for the monolithic fleet."""
        return {}

    def token_streams(self) -> dict[int, list[int]]:
        """Completed token stream per request id across every replica — the
        byte-parity surface benchmarks compare between fleet topologies."""
        out: dict[int, list[int]] = {}
        for r in self.replicas:
            for rid, res in r.engine.results.items():
                out[rid] = list(res.tokens)
        return out

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, cfg, params, *, chips: int,
              fleet: FleetConfig | None = None, slo: SLO | None = None,
              profile: recompile.SystemProfile | None = None,
              batch_jobs: Sequence[tuple[int, int]] = (),
              batch_step_s: float = 1.0, batch_ckpt_every: int = 5,
              store_factory=None) -> "FleetManager":
        """Assemble a complete fleet on a fresh cluster: scheduler, invocation
        service, serving container, optional BATCH coexistence jobs
        (``batch_jobs`` = [(chips, total_steps), ...])."""
        from repro.serving.service import serving_container

        fleet = fleet or FleetConfig()
        # ---- replica width: fixed by mesh_shape, or chosen over
        # mesh_options by the width-vs-count policy under this cluster's
        # chip budget (the chosen point lands in the timeline + report) ----
        mesh_shape = fleet.mesh_shape
        width_decision: dict = {}
        if fleet.mesh_options:
            base = profile or recompile.detect_profile()
            per_chip = {tuple(o): replica_bytes_per_chip(cfg, fleet, tuple(o))
                        for o in fleet.mesh_options}
            mesh_shape, reason = choose_replica_width(
                options=[tuple(o) for o in fleet.mesh_options],
                chip_budget=chips, bytes_per_chip=per_chip,
                hbm_bytes=base.hbm_bytes, min_replicas=fleet.min_replicas)
            if int(np.prod(mesh_shape)) == 1:
                mesh_shape = None  # narrowest point: plain 1-chip replicas
            width_decision = {
                "mesh_shape": list(mesh_shape) if mesh_shape else [1],
                "chips_per_replica": (int(np.prod(mesh_shape))
                                      if mesh_shape else 1),
                "reason": reason,
                "options": [list(o) for o in fleet.mesh_options],
                "bytes_per_chip": {
                    "x".join(map(str, k)): v for k, v in per_chip.items()},
            }
            fleet = dataclasses.replace(fleet, mesh_shape=mesh_shape)
        if mesh_shape is not None:
            if profile is None or profile.chips != int(np.prod(mesh_shape)):
                profile = recompile.detect_profile(tuple(mesh_shape))
        else:
            profile = profile or recompile.detect_profile()
        service = InvocationService(scheduler.Cluster(chips=chips))
        spec = None
        if fleet.spec_k > 0:
            from repro.serving.speculative import SpecConfig
            spec = SpecConfig(k=fleet.spec_k, proposer=fleet.spec_proposer,
                              draft_arch=fleet.spec_draft_arch)
        cont = serving_container(
            cfg, params, slots=fleet.slots, max_len=fleet.max_len,
            prompt_buckets=fleet.prompt_buckets, sync_every=fleet.sync_every,
            prefix_cache_bytes=int(fleet.prefix_cache_mb * (1 << 20)) or None,
            spec=spec, page_size=fleet.page_size, kv_pages=fleet.kv_pages,
            kv_watermark=fleet.kv_watermark,
            prefill_chunk_tokens=fleet.prefill_chunk_tokens,
            mesh_shape=mesh_shape,
            artifact_store=fleet.artifact_store)
        batch = None
        if batch_jobs:
            batch = BatchWorkload(service.cluster, step_s=batch_step_s,
                                  ckpt_every=batch_ckpt_every,
                                  store_factory=store_factory)
            for bchips, bsteps in batch_jobs:
                batch.submit(chips=bchips, total_steps=bsteps)
            service.cluster.run(until=service.cluster.now)
        return cls(service, cont, profile, config=fleet,
                   autoscaler=Autoscaler(slo or SLO(), fleet.min_replicas,
                                         fleet.max_replicas),
                   batch=batch, width_decision=width_decision)
