#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run leases a chip through ``InvocationService.acquire_serving`` with
the profile ``recompile.detect_profile()`` gives, deploys the serving
container of the cell's configuration with weights made on the device from
``--seed``, warms it (``ServingExecutor.warmup``), serves a warm phase of
the cell's traffic and then the measured window of ``--seconds`` through
``ServingExecutor.submit`` / ``step``. Everything up to the window's start
is ``setup_s``. After the window the program's state is freed and the plain
reference checks a sample of the served tokens (``bench/check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records the warm phase and the window, and the
result carries the per-layer metrics, the device's busy time and a
breakdown. The last line of stdout is one JSON object; the compared numbers
and their limits are the last lines of stderr and the last key of that
object. Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# a backlog cell's warm phase may not take longer than this
WARM_LIMIT_S = 120.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs lowered (and backend compiles) while ``on``."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.on = False
        self.lowered = self.compiled = 0
        self._events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lowered",
                        dispatch.BACKEND_COMPILE_EVENT: "compiled"}
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        name = self._events.get(event)
        if self.on and name is not None:
            setattr(self, name, getattr(self, name) + 1)


class Setup:
    """A leased, deployed and warmed serving engine for one configuration
    of ``family`` (``bench/families/<model_type>.py``)."""

    def __init__(self, family, name: str, conf: dict, seed: int, clock):
        import jax

        from bench import model as bm
        from repro.core import recompile, scheduler
        from repro.core.invocation import InvocationService
        from repro.serving.service import serving_container

        self.cfg = family.arch_config(name, conf)
        t = clock()
        params = bm.make_params(family, seed, conf, self.cfg)
        jax.block_until_ready(params)
        self.weights_s = clock() - t
        profile = recompile.detect_profile()
        self.service = InvocationService(
            scheduler.Cluster(chips=profile.chips))
        cont = serving_container(self.cfg, params,
                                 **bm.container_kwargs(conf["engine"]))
        del params
        t = clock()
        self.ex = self.service.acquire_serving("bench", cont, profile)
        self.deploy_s = clock() - t
        t = clock()
        self.manifest = self.ex.warmup()
        self.warmup_s = clock() - t

    def close(self) -> None:
        self.ex.release()
        self.ex.engine = None
        self.ex = self.service = None


def _serve(serving, traffic, seconds: float, trace_dir, counter):
    """Warm phase, then the window. Returns (client, anchor): ``anchor`` is
    the window time at which the trace's anchor span opened."""
    import jax

    from bench.client import CLOCK, SPAN, Client

    client = Client(serving.ex, traffic.items)
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        if traffic.open_after_finished:
            client.submit_backlog()
            k = traffic.open_after_finished
            client.run(CLOCK() + WARM_LIMIT_S, stop=lambda: client.finished >= k)
            if client.finished < k:
                raise RuntimeError(f"{client.finished} of {k} requests finished "
                                   f"in the {WARM_LIMIT_S} s warm phase")
            client.t0 = CLOCK()
        else:
            first = min(it.due_s for it in traffic.items)
            client.t0 = CLOCK() - first
            client.run(client.t0)
        counter.on = True
        with SPAN("bench.window"):
            anchor = CLOCK() - client.t0
            client.run(client.t0 + seconds)
        counter.on = False
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return client, anchor


def served(eng, recs, traffic, close: float):
    """(rid, prompt, tokens) of every request finished by the close with
    all its tokens, and the count of those that finished short."""
    by_rid = {r.rid: r for r in recs}
    cases, short = [], 0
    for rid, res in eng.results.items():
        r = by_rid[rid]
        if r.done is None or r.done > close:
            continue
        if len(res.tokens) != r.max_new:
            short += 1
        else:
            cases.append((rid, traffic.items[rid].prompt, list(res.tokens)))
    return cases, short


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def run_cell(bench, family, name: str, seed: int, seconds: float,
             traced: bool) -> dict:
    """One run of cell ``name``, whose configuration is of ``family``;
    returns the result object."""
    import jax

    from bench import check, flops, trace as tr, window
    from bench.client import CLOCK

    cell = bench.cell(name)
    conf = bench.config(cell)
    mix = bench.mix(cell)
    limits = bench.limits(cell)
    dev = jax.devices()[0]
    traffic = bench.generator(mix).make(mix, seed, seconds,
                                        conf["model"]["vocab_size"])
    counter = CompileCounter()
    serving = Setup(family, cell["config"], conf, seed, CLOCK)
    log(f"bench: weights {serving.weights_s:.3f} s, deploy "
        f"{serving.deploy_s:.3f} s, warmup {serving.warmup_s:.3f} s "
        f"({serving.manifest['boot']['warmup_compiles']} programs, boot "
        f"{serving.manifest['boot']['path']}); tiers "
        + " ".join(f"{a}={c['provider']}" for a, c in
                   sorted(serving.manifest.get("apis", {}).items())))
    trace_dir = None
    if traced:
        trace_dir = bench.root / "bench_out" / "trace" / f"{name}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    client, anchor = _serve(serving, traffic, seconds, trace_dir, counter)
    setup_s = client.t0 - T_START
    eng = serving.ex.engine
    memory_peak = _memory_peak()
    recs, prefill = client.relative()
    log(f"bench: window {seconds} s: {counter.lowered} programs lowered and "
        f"{counter.compiled} compiled inside it; engine stats "
        + json.dumps({k: eng.stats[k] for k in (
            "decode_steps", "chunk_prefill_calls", "preemptions",
            "prefix_hit_tokens", "retired")}))

    # ---- what the window served, then the program's state is freed ----
    cases, short = served(eng, recs, traffic, seconds)
    due = window.due_in(recs, seconds)
    attempted = len(due) if due else sum(
        1 for r in recs if r.admit is not None and r.admit <= seconds
        and (r.done is None or r.done >= 0.0))
    spans = {"setup_s": setup_s, "weights_s": serving.weights_s,
             "deploy_s": serving.deploy_s, "warmup_s": serving.warmup_s}
    manifest = serving.manifest
    serving.close()
    del eng, serving, client
    gc.collect()
    log(f"bench: {sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB "
        f"still on the device after the program's state was freed")

    t = CLOCK()
    sample = check.sample(cases, seed, limits["sample_tokens"])
    pad = check.pad_length(conf["engine"]["max_len"], family.Q_BLOCK)
    gaps = check.served_gaps(family, conf, seed, sample, pad)
    gap = float(gaps.max()) if len(gaps) else float("nan")
    log(f"bench: reference over {len(sample)} requests, {len(gaps)} served "
        f"tokens, {CLOCK() - t:.3f} s")
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"]["limit"],
                      "rule": "<="},
        "short_outputs": {"value": short, "limit": 0, "rule": "<="},
        "checked_tokens": {"value": int(len(gaps)),
                           "limit": limits["checked_tokens_min"],
                           "rule": ">="},
    }
    correct = all(
        (c["value"] <= c["limit"]) if c["rule"] == "<="
        else (c["value"] >= c["limit"]) for c in checks.values())

    # ---- metrics ----
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    trace = None
    if traced:
        planes = tr.planes_of(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for line in tr.describe(planes, k=6):
            log(f"trace: {line}")
        trace = tr.reduce(planes, float(seconds), anchor)
        device["busy_s"] = trace.busy_s()
        device["window_s"] = float(seconds)
    ctx = types.SimpleNamespace(
        cell=cell, conf=conf, family=family, close=float(seconds),
        recs=recs, prefill=prefill, trace=trace, spans=spans,
        manifest=manifest,
        peaks=None if dev.platform == "cpu" else flops.peaks(dev.device_kind))
    metrics, missing = {}, []
    for m in bench.metrics(cell, traced):
        value = bench.reader(m["name"]).read(ctx)
        if value is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if missing and dev.platform == "tpu":
        # BENCHMARK.json declares these for this cell: on the chip a reader
        # that finds nothing means a name it looks for is not in the trace
        log(f"bench: metrics read: {json.dumps(metrics)}")
        raise RuntimeError(f"declared for {name} but read nothing on the "
                           f"chip: {', '.join(missing)}")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(short), "metrics": metrics, "device": device}
    if trace is not None:
        out["breakdown"] = {"device_ops": trace.top_ops(),
                            "idle_gaps": trace.idle_gaps()}
    out["checks"] = checks
    return out


def _configure_jax() -> None:
    import jax

    from repro.core import recompile

    recompile.enable_compile_cache()
    # cache every program, however quickly it compiled: the second run of a
    # cell must compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, *, root=ROOT, require_tpu: bool = True,
         compile_cache: bool = True) -> int:
    """``require_tpu`` and ``compile_cache`` are off only in tests, which
    drive a run on the CPU and may not touch the process's cache."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.Bench(root)
    cell = bench.cell(args.workload)
    try:
        family = bench.family(bench.config(cell))
    except FileNotFoundError as err:
        log(f"bench: {err}")
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"bench: platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(devices)} device(s), jax {jax.__version__}")
    if require_tpu and dev.platform != "tpu":
        log(f"bench: no TPU (JAX sees {dev.platform!r}); refusing to run")
        return 2
    if len(devices) < cell["chips"]:
        log(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
            f"{len(devices)}")
        return 2
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro  # noqa: F401
    except ImportError as err:
        log(f"bench: the program is not in this checkout ({err})")
        return 2
    if compile_cache:
        _configure_jax()
    out = run_cell(bench, family, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
