"""XContainer — the performance-portable container (paper Figure 2).

The paper's container stack, translated to JAX (DESIGN.md §1):

    domain layer      = the model: an ArchConfig + entrypoints (train_step /
                        prefill / decode) built from `models/`
    XaaS layer        = accelerated-API *requirements* (which hooks the
                        program calls) + logical sharding annotations
    provider layer    = a SystemProfile supplying hook implementations, mesh,
                        and the XLA compiler for the target chip

An ``XContainer`` is the shippable unit: a *recipe* that can be deployed onto
any provider profile. ``deploy()`` runs the paper's pipeline — bind hooks
(flexible hooked libraries), install sharding rules, lower to IR, compile at
the target (deployment recompilation) — and returns a ``Deployment`` holding
the compiled artifact plus everything accounting/roofline need.

Containers never contain weights. Weights are data (the paper's "data
gravity" lives in the checkpoint store); containers are programs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax

from repro.core import hooks, recompile
from repro.distributed import sharding as shd

__all__ = ["XContainer", "Deployment", "build_mesh"]


def build_mesh(profile: recompile.SystemProfile) -> jax.sharding.Mesh:
    """Materialize the profile's mesh on the current backend's devices."""
    return shd.make_mesh(profile.mesh_shape, profile.mesh_axes)


@dataclasses.dataclass
class Deployment:
    """A container deployed on one provider system."""

    container: "XContainer"
    profile: recompile.SystemProfile
    mesh: jax.sharding.Mesh
    binding: hooks.Binding
    rules: shd.Rules
    artifacts: dict[str, recompile.CompiledArtifact]

    def artifact(self, entrypoint: str) -> recompile.CompiledArtifact:
        return self.artifacts[entrypoint]

    def __call__(self, entrypoint: str, *args, **kwargs):
        """Invoke a deployed entrypoint (data plane: compiled XLA only)."""
        return self.artifacts[entrypoint](*args, **kwargs)

    def providers(self) -> dict[str, str]:
        return self.binding.providers()

    def manifest(self) -> dict:
        """The specialization manifest: which tier serves each accelerated
        API on this deployment, with probe provenance
        (docs/kernel-portability.md), plus how each entrypoint's executable
        came to exist (cold compile / warm cache / IR restore — the boot
        ladder, docs/ir-containers.md)."""
        m = self.binding.manifest()
        return {
            "container": self.container.name,
            "profile": self.profile.name,
            "chip": self.profile.chip,
            "chips": self.profile.chips,
            # resolved mesh geometry + sharding rule set, next to the kernel
            # tiers: the specialization record answers "what grid does this
            # deployment span and how do logical axes land on it" the same
            # way it answers "which tier serves each API"
            "mesh": {
                "shape": tuple(int(s) for s in self.mesh.devices.shape),
                "axes": tuple(self.mesh.axis_names),
            },
            "sharding_rules": shd.rule_summary(self.rules),
            "apis": m["apis"],
            "entrypoint_boot": {
                ep: {"boot": art.boot, "cache_hit": art.cache_hit,
                     "lower_s": round(art.lower_s, 6),
                     "compile_s": round(art.compile_s, 6)}
                for ep, art in self.artifacts.items()
            },
        }


@dataclasses.dataclass
class XContainer:
    """A performance-portable program recipe.

    entrypoints: name -> (fn, make_args) where ``make_args(mesh)`` returns
    (args, kwargs) of ShapeDtypeStructs (dry-run) or real arrays, already
    annotated with shardings where needed; ``fn`` is traced under the hook
    binding + sharding rules, so the *same recipe* specializes per target.
    """

    name: str
    entrypoints: dict[str, tuple[Callable, Callable]]
    rules_2d: shd.Rules = dataclasses.field(default_factory=lambda: dict(shd.RULES_2D))
    rules_3d: shd.Rules = dataclasses.field(default_factory=lambda: dict(shd.RULES_3D))
    hook_overrides: Mapping[str, str] | None = None
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)
    # persistent AOT artifact store carried WITH the container (the "IR
    # half" of an XaaS source+IR container): deploy() persists compiled
    # entrypoints here and restores them in later processes, and serving
    # engines booted from this container IR-boot their data plane from it
    artifact_store: Any = None

    def rules_for(self, profile: recompile.SystemProfile) -> shd.Rules:
        return self.rules_3d if "pod" in profile.mesh_axes else self.rules_2d

    def deploy(
        self,
        profile: recompile.SystemProfile,
        *,
        mesh: jax.sharding.Mesh | None = None,
        compiler: recompile.DeploymentCompiler | None = None,
        entrypoints: list[str] | None = None,
        hook_overrides: Mapping[str, str] | None = None,
        probe: bool = True,
        artifact_store=None,
    ) -> Deployment:
        """Deploy onto `profile`: probe + bind hooks, install sharding rules,
        lower, compile. With ``probe`` (default) every candidate tier must
        pass its deploy-time probe before binding (hooks.bind); the chosen
        tier per API lands in ``meta["specialization"][profile.name]`` so
        warm re-deployments can report exactly what serves traffic."""
        compiler = compiler or recompile.DEFAULT_COMPILER
        store = (artifact_store if artifact_store is not None
                 else self.artifact_store)
        mesh = mesh if mesh is not None else build_mesh(profile)
        binding = hooks.bind(
            profile, overrides=hook_overrides or self.hook_overrides,
            probe=probe)
        rules = self.rules_for(profile)
        artifacts: dict[str, recompile.CompiledArtifact] = {}
        names = entrypoints or list(self.entrypoints)
        for ep in names:
            fn, make_args = self.entrypoints[ep]
            args, kwargs, jit_kwargs = make_args(mesh)
            with mesh, shd.use_rules(rules, mesh), hooks.use(binding):
                artifacts[ep] = compiler.deploy(
                    fn,
                    f"{self.name}/{ep}",
                    profile,
                    args=args,
                    kwargs=kwargs,
                    jit_kwargs=jit_kwargs,
                    store=store,
                    store_extra={"tiers": binding.tier_fingerprint()},
                )
        dep = Deployment(
            container=self,
            profile=profile,
            mesh=mesh,
            binding=binding,
            rules=rules,
            artifacts=artifacts,
        )
        self.meta.setdefault("specialization", {})[profile.name] = dep.manifest()
        return dep
