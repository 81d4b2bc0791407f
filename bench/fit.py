#!/usr/bin/env python3
"""Rehearse a cell's memory without the chip: compile the largest programs
its warmup builds for a described TPU v5e and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/fit.py [--workload <cell> ...]

``--pin api=provider`` binds another tier for one API, to see whether a
refusal is that tier's. The programs are the weight maker, the prefill chunk at (slots, chunk cap)
and the paged decode step at every slot, under the tiers a v5e binds. A
compile that passes is not a chip run; a compile the TPU compiler refuses
(too little memory, a kernel it cannot lower) costs no chip time here. This
is a tool to run by hand, not a test.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GB = 1e9


def _report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    args, out = m.argument_size_in_bytes, m.output_size_in_bytes
    tmp, alias = m.temp_size_in_bytes, m.alias_size_in_bytes
    print(f"  {name}: arguments {args / GB:.3f} GB, outputs {out / GB:.3f} "
          f"GB (aliased {alias / GB:.3f}), temporaries {tmp / GB:.3f} GB; "
          f"peak about {(args + out - alias + tmp) / GB:.3f} GB", flush=True)


def fit(bench, name: str, one_chip, pins=None) -> None:
    import jax
    import jax.numpy as jnp

    from bench import weights
    from repro.core import hooks, recompile
    from repro.kernels import ops  # noqa: F401  (registers the tiers)
    from repro.models import transformer
    from repro.serving.sampling import SamplingParams, sample_batched

    cell = bench.cell(name)
    conf = bench.config(cell)
    family = bench.family(conf)
    eng = conf["engine"]
    cfg = family.arch_config(cell["config"], conf)
    dt = jnp.dtype(cfg.param_dtype)
    slots, cap, ps = eng["slots"], eng["prefill_chunk_tokens"], \
        eng["page_size"]
    nb = eng["max_len"] // ps

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    key = on_chip(jax.eval_shape(lambda: weights.seed_key(0)))
    params = on_chip(jax.eval_shape(
        lambda k: family.program_tree(k, conf, dt), weights.seed_key(0)))
    states = on_chip(jax.eval_shape(
        lambda: transformer.init_paged_states(cfg, eng["kv_pages"], ps, dt)))
    i32 = lambda *s: on_chip(jax.ShapeDtypeStruct(s, jnp.int32))  # noqa
    binding = hooks.bind(recompile.TPU_V5E, overrides=pins)
    print(f"{name}: tiers " + " ".join(
        f"{a}={c.provider}" for a, c in sorted(binding.choices.items())))

    make = jax.jit(lambda k: family.program_tree(k, conf, dt))
    _report("weights", make.lower(key).compile())

    def prefill(p, t, s, start, lens, bt):
        return transformer.prefill_chunk(p, cfg, t, s, start, lens,
                                         block_tables=bt, page_size=ps)

    def decode(p, k, t, s, lens, bt, temp, topk):
        return transformer.decode_and_sample(
            p, cfg, t, s, lens, k,
            lambda kk, lg: sample_batched(kk, lg,
                                          SamplingParams(temp, topk)),
            block_tables=bt, page_size=ps)

    rng = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    f32 = on_chip(jax.ShapeDtypeStruct((slots,), jnp.float32))
    with hooks.use(binding):
        _report(f"prefill chunk ({slots} x {cap})", jax.jit(prefill).lower(
            params, i32(slots, cap), states, i32(slots), i32(slots),
            i32(slots, nb)).compile())
        _report(f"decode step ({slots} rows)", jax.jit(decode).lower(
            params, rng, i32(slots), states, i32(slots), i32(slots, nb),
            f32, i32(slots)).compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append",
                    help="cell name (default: every cell)")
    ap.add_argument("--pin", action="append", default=[],
                    help="api=provider: bind this tier instead (a witness "
                         "run of another path of the program)")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness

    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.Bench(ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = args.workload or [w["name"] for w in bench.spec["workloads"]]
    for name in names:
        fit(bench, name, one_chip,
            dict(p.split("=", 1) for p in args.pin) or None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
