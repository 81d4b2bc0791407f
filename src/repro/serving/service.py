"""Serving-as-a-Service glue: an ``XContainer`` whose deployment boots a
``ServingEngine``.

This is how serving becomes a first-class leased XaaS workload instead of a
hand-constructed engine: the container's ``meta['engine_factory']`` is the
boot hook ``InvocationService.acquire_serving`` calls after scheduling a
SERVICE-class lease and deploying the container. The container also carries a
real ``decode`` entrypoint through the deployment compiler, so the lease's
ledger meters decode FLOPs from the *compiled artifact* (billing from the
compiled truth, same as every other XaaS workload) rather than from user
claims.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import container as xcontainer
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.serving.engine import ServingEngine

__all__ = ["serving_container"]


def serving_container(
    cfg,
    params,
    *,
    slots: int = 8,
    max_len: int = 512,
    prompt_buckets: tuple[int, ...] = (32, 128, 512),
    fused: bool = True,
    sync_every: int = 1,
    prefix_cache_bytes: int | None = None,
    spec=None,
    draft_params=None,
    page_size: int | None = None,
    kv_pages: int | None = None,
    kv_watermark: float = 0.05,
    prefill_chunk_tokens: int | None = None,
    role: str = "both",
    name: str | None = None,
    artifact_store=None,
    mesh_shape: tuple[int, ...] | None = None,
    rules=None,
) -> xcontainer.XContainer:
    """Build a deployable serving container for one model.

    ``deploy()`` compiles the ``decode`` entrypoint (the metering artifact);
    ``meta['engine_factory'](deployment)`` boots the continuous-batching
    engine bound to that deployment. ``spec`` (a
    ``repro.serving.speculative.SpecConfig``) turns on speculative decoding
    in every engine booted from this container; ``draft_params`` optionally
    supplies trained draft-model weights for the "draft" proposer kind.
    ``artifact_store`` (a ``repro.checkpoint.store.ArtifactStore``) makes
    the container a source+IR container: deployed entrypoints and the
    engine's whole data-plane bundle persist as serialized executables, so
    a later PROCESS boots from cached IR instead of re-tracing (the
    IR-boot rung — docs/ir-containers.md).

    ``mesh_shape`` makes every engine booted from this container a
    *sharded* replica: its data plane traces under ``use_rules`` on a mesh
    of that geometry (the deployment's own mesh when it matches, else one
    built from the local devices), params and KV pools get NamedShardings
    from the logical-axis rule trees, and the SERVICE lease must be
    acquired against a profile whose ``chips`` equals the mesh size so
    metering bills every chip the replica spans. ``rules`` overrides the
    deployment's logical-axis rule set (default: the deployment's own —
    RULES_2D/RULES_3D by profile). ``mesh_shape=None`` keeps today's
    single-device engine untouched (the portability floor).
    """
    dt = jnp.dtype(cfg.activ_dtype)

    def decode_fn(params_, tokens, states, lengths):
        return transformer.decode_step(params_, cfg, tokens, states, lengths)

    def make_args(mesh):
        pshapes = jax.eval_shape(lambda: transformer.init_model(jax.random.key(0), cfg))
        sshapes = jax.eval_shape(lambda: transformer.init_states(cfg, slots, max_len, dt))
        if cfg.frontend == "audio":
            tok = jax.ShapeDtypeStruct((slots, cfg.num_codebooks), jnp.int32)
        else:
            tok = jax.ShapeDtypeStruct((slots,), jnp.int32)
        lens = jax.ShapeDtypeStruct((slots,), jnp.int32)
        return (pshapes, tok, sshapes, lens), {}, {}

    def engine_factory(deployment) -> ServingEngine:
        # the engine inherits the deployment's probed hook binding + its
        # specialization manifest: traffic is served by exactly the tiers
        # deploy() bound, and warmup() reports them
        proposer = None
        if spec is not None and draft_params is not None:
            from repro.serving.speculative import make_proposer
            proposer = make_proposer(spec, cfg, draft_params=draft_params)
        mesh = None
        eng_rules = None
        if mesh_shape is not None:
            # prefer the deployment's own mesh (built from the lease's
            # profile) so the engine shards exactly the devices the lease
            # granted; build one only when the profile is single-device
            # (e.g. a sharded container deployed for offline tracing)
            dep_geom = tuple(int(s) for s in deployment.mesh.devices.shape)
            if dep_geom == tuple(mesh_shape):
                mesh = deployment.mesh
            else:
                axes = ("data", "model")[-len(mesh_shape):]
                mesh = shd.make_mesh(tuple(mesh_shape), axes)
            eng_rules = rules if rules is not None else deployment.rules
        return ServingEngine(
            cfg, params, slots=slots, max_len=max_len,
            prompt_buckets=prompt_buckets, fused=fused, sync_every=sync_every,
            prefix_cache_bytes=prefix_cache_bytes,
            spec=spec, proposer=proposer,
            page_size=page_size, kv_pages=kv_pages,
            kv_watermark=kv_watermark,
            prefill_chunk_tokens=prefill_chunk_tokens,
            role=role,
            artifact_store=artifact_store,
            mesh=mesh, rules=eng_rules,
            binding=deployment.binding, manifest=deployment.manifest())

    # geometry in the name: the warm-deployment cache keys on (name, profile),
    # so two serving containers for the same arch but different slot/cache
    # geometry (incl. paged vs contiguous KV) must never alias each other's
    # compiled decode artifact
    paged_tag = f"-p{page_size}x{kv_pages or 0}" if page_size else ""
    role_tag = f"-{role}" if role != "both" else ""
    mesh_tag = ("-m" + "x".join(str(int(d)) for d in mesh_shape)
                if mesh_shape else "")
    return xcontainer.XContainer(
        name=name or (f"serve-{cfg.name}-b{slots}x{max_len}"
                      f"{paged_tag}{role_tag}{mesh_tag}"),
        entrypoints={"decode": (decode_fn, make_args)},
        meta={
            "engine_factory": engine_factory,
            "arch": cfg.name,
            "slots": slots,
            "max_len": max_len,
        },
        artifact_store=artifact_store,
    )
