"""Backend specialization: deploy-time probes, tier fallback, and the
container specialization manifest (docs/kernel-portability.md).

The contract under test: a tier that cannot actually compile/run on the
target must be *rejected at bind time* and dispatch must fall back to the
next priority, with the rejection recorded in the manifest — never an
exception escaping from inside a deployed program.
"""
import dataclasses
import functools
import importlib.util
import itertools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import container as xc
from repro.core import hooks, recompile
from repro.kernels import compat, ops, ref


@dataclasses.dataclass(frozen=True)
class FakeProfile:
    """Minimal stand-in for a SystemProfile with a controllable library set."""

    name: str
    chip: str
    providers: tuple

    def supports(self, provider):
        return provider in self.providers


_uid = itertools.count()


def _fresh_api():
    """Register a throwaway accelerated API with a hi/lo tier pair where the
    hi tier's probe fails. Returns (api_name, probe_call_counts)."""
    name = f"_spec_probe_op_{next(_uid)}"
    hooks.register_api(name, "(x) -> x", lambda x: x * 0 + 1.0)
    calls = {"hi": 0, "lo": 0}

    def hi_probe(profile):
        calls["hi"] += 1
        raise AttributeError("module has no attribute 'CompilerParams'")

    def lo_probe(profile):
        calls["lo"] += 1

    hooks.register_impl(
        name, "tier-hi", lambda x: x * 0 + 2.0,
        supports=lambda p: p.supports("tier-hi"), priority=20, probe=hi_probe)
    hooks.register_impl(
        name, "tier-lo", lambda x: x * 0 + 3.0,
        supports=lambda p: p.supports("tier-lo"), priority=10, probe=lo_probe)
    return name, calls


def test_probe_failure_falls_back_to_next_tier():
    api, calls = _fresh_api()
    prof = FakeProfile(f"fake-{api}", f"chip-{api}", ("tier-hi", "tier-lo"))
    binding = hooks.bind(prof, probe=True)
    assert binding.providers()[api] == "tier-lo"
    choice = binding.choices[api]
    assert choice.probed
    assert choice.rejected[0][0] == "tier-hi"
    assert "CompilerParams" in choice.rejected[0][1]
    # the bound fn is really the lo tier
    with hooks.use(binding):
        np.testing.assert_allclose(
            np.asarray(hooks.call(api, jnp.zeros(2))), 3.0)


def test_all_probes_failing_reaches_portable_floor():
    api, _ = _fresh_api()
    # profile only offers the (broken) hi tier -> reference must serve
    prof = FakeProfile(f"fake-{api}", f"chip-{api}", ("tier-hi",))
    binding = hooks.bind(prof, probe=True)
    assert binding.providers()[api] == "portable"
    assert binding.choices[api].rejected == (
        ("tier-hi", "AttributeError: module has no attribute "
         "'CompilerParams'"),)
    with hooks.use(binding):
        np.testing.assert_allclose(
            np.asarray(hooks.call(api, jnp.zeros(2))), 1.0)


def test_probe_results_cached_per_chip():
    api, calls = _fresh_api()
    prof = FakeProfile(f"fake-{api}", f"chip-{api}", ("tier-hi", "tier-lo"))
    hooks.bind(prof, probe=True)
    hooks.bind(prof, probe=True)  # warm re-bind: no re-probe
    assert calls == {"hi": 1, "lo": 1}
    # a different chip kind re-probes (different local toolchain assumption)
    other = FakeProfile(f"fake2-{api}", f"chip2-{api}", ("tier-hi", "tier-lo"))
    hooks.bind(other, probe=True)
    assert calls == {"hi": 2, "lo": 2}


def test_reregister_invalidates_stale_probe_verdict():
    api, _ = _fresh_api()
    prof = FakeProfile(f"fake-{api}", f"chip-{api}", ("tier-hi", "tier-lo"))
    assert hooks.bind(prof, probe=True).providers()[api] == "tier-lo"
    # ship a fixed implementation under the same provider tag: the cached
    # failure verdict for the old one must not keep rejecting it
    hooks.register_impl(
        api, "tier-hi", lambda x: x * 0 + 4.0,
        supports=lambda p: p.supports("tier-hi"), priority=20,
        probe=lambda profile: None)
    assert hooks.bind(prof, probe=True).providers()[api] == "tier-hi"


def test_unprobed_bind_keeps_legacy_selection():
    api, calls = _fresh_api()
    prof = FakeProfile(f"fake-{api}", f"chip-{api}", ("tier-hi", "tier-lo"))
    binding = hooks.bind(prof)  # probe=False: priority wins, nothing runs
    assert binding.providers()[api] == "tier-hi"
    assert calls == {"hi": 0, "lo": 0}


def test_pinned_override_is_not_probed():
    api, calls = _fresh_api()
    prof = FakeProfile(f"fake-{api}", f"chip-{api}", ("tier-hi", "tier-lo"))
    binding = hooks.bind(prof, overrides={api: "tier-hi"}, probe=True)
    assert binding.providers()[api] == "tier-hi"
    assert calls["hi"] == 0  # a pin is an operator's explicit order


# ---------------------------------------------------------------------------
# The real tiers on the CPU-CI profile
# ---------------------------------------------------------------------------
def test_cpu_interpret_profile_binds_pallas_interpret():
    binding = hooks.bind(recompile.CPU_INTERPRET, probe=True)
    prov = binding.providers()
    for api in ("attention", "decode_attention", "rmsnorm", "moe_mlp"):
        assert prov[api] == "pallas-interpret", (api, prov[api])
    assert prov["mlstm"] == "xla-blocked"
    man = binding.manifest()
    assert man["apis"]["attention"]["probed"]


def test_interpret_tier_numerics_match_ref():
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(k1, (1, 64, 2, 16))
    k = jax.random.normal(k2, (1, 64, 1, 16))
    v = jax.random.normal(k3, (1, 64, 1, 16))
    binding = hooks.bind(recompile.CPU_INTERPRET, probe=True)
    with hooks.use(binding):
        got = hooks.call("attention", q, k, v, causal=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_container_deploy_records_manifest():
    def fn(q, k, v):
        return hooks.call("attention", q, k, v, causal=True)

    def make_args(mesh):
        q = jax.ShapeDtypeStruct((1, 16, 2, 8), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, 16, 1, 8), jnp.float32)
        return (q, kv, kv), {}, {}

    cont = xc.XContainer(name="spec-demo", entrypoints={"attn": (fn, make_args)})
    dep = cont.deploy(recompile.CPU_INTERPRET)
    man = dep.manifest()
    assert man["profile"] == "cpu-pallas-interpret"
    assert man["apis"]["attention"]["provider"] == "pallas-interpret"
    # deploy() mirrors the manifest into the container's meta, keyed by
    # profile, so a shipped recipe carries the record of every specialization
    stored = cont.meta["specialization"]["cpu-pallas-interpret"]
    assert stored["apis"] == man["apis"]
    # and the portable floor stays portable
    dep_cpu = cont.deploy(recompile.PORTABLE_CPU)
    assert dep_cpu.manifest()["apis"]["attention"]["provider"] == "portable"


# ---------------------------------------------------------------------------
# cost_analysis normalization (the version shim's other half)
# ---------------------------------------------------------------------------
def test_normalize_cost_analysis_formats():
    # the installed JAX returns a dict, or None without a cost model
    assert compat.normalize_cost_analysis(None) == {}
    assert compat.normalize_cost_analysis({"flops": 1.0}) == {"flops": 1.0}
    raw = jax.jit(lambda a: a @ a).lower(jnp.zeros((8, 8))).compile()
    assert compat.normalize_cost_analysis(raw.cost_analysis())["flops"] > 0
    with pytest.raises(TypeError):
        compat.normalize_cost_analysis([{"flops": 2.0}])


def test_compiled_artifact_cost_analysis_normalized():
    x = jnp.zeros((32, 32))
    comp = recompile.DeploymentCompiler()
    art = comp.deploy(lambda a: a @ a, "norm-demo", recompile.PORTABLE_CPU,
                      args=(x,))
    cost = art.cost_analysis()
    assert isinstance(cost, dict)
    assert art.flops == pytest.approx(2 * 32**3, rel=0.05)


# ---------------------------------------------------------------------------
# chip_smoke.py: its phases at smoke size on the CPU (interpret tier), and
# its entry point's refusal to run without a TPU
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _chip_smoke():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations here
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_smoke_size():
    smoke = _chip_smoke()
    geo = smoke.Geometry(arch="qwen2-0.5b-smoke", requests=4, prompt_lo=8,
                         prompt_hi=24, max_new=4, slots=4, max_len=64,
                         prompt_buckets=(16, 32), page_size=8)
    lines = []
    out = smoke.run_phases(geo, recompile.CPU_INTERPRET,
                           expect_tier="pallas-interpret", log=lines.append)
    assert len(out["contiguous"]) == len(out["paged"]) == 4
    text = "\n".join(lines)
    assert "decode_attention=pallas-interpret" in text
    assert "paged_decode_attention=pallas-interpret" in text
    assert "streams: 4/4 identical" in text


def test_chip_smoke_refuses_the_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert _chip_smoke().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
