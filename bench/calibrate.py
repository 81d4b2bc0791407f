#!/usr/bin/env python3
"""Readings a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds <n,...>

For every seed, in one process: the program serves the cell's traffic
through the timed path for a short window at the cell's own load, and the
plain reference gives the widest logit gap of a sample of served tokens
(the lower reading, as a run compares it); then the control, the reference
in float8 at the same positions, gives the widest gap of the tokens it puts
first (the upper reading). The limit lies between the largest lower and the
smallest upper reading (``bench/limits/<cell>.json``). The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    import jax

    from bench import check, harness, run
    from bench.client import CLOCK

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    run._configure_jax()
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    conf, mix = bench.config(cell), bench.mix(cell)
    family = bench.family(conf)
    limits = bench.limits(cell)
    pad = check.pad_length(conf["engine"]["max_len"], family.Q_BLOCK)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        traffic = bench.generator(mix).make(mix, seed, args.seconds,
                                            conf["model"]["vocab_size"])
        serving = run.Setup(family, cell["config"], conf, seed, CLOCK)
        client, _ = run._serve(serving, traffic, args.seconds, None,
                            run.CompileCounter())
        recs, _ = client.relative()
        cases, short = run.served(serving.ex.engine, recs, traffic,
                                  args.seconds)
        serving.close()
        del serving, client
        gc.collect()
        sample = check.sample(cases, seed, limits["sample_tokens"])
        t = CLOCK()
        prog = check.served_gaps(family, conf, seed, sample, pad)
        t_ref = CLOCK() - t
        ctrl = check.control_gaps(family, conf, seed, sample, pad)
        row = {"seed": seed, "requests": len(sample), "tokens": len(prog),
               "short": short, "program_gap": float(prog.max()),
               "program_gap_p99": float(sorted(prog)[int(0.99 * len(prog))]),
               "control_gap": float(ctrl.max()),
               "control_positions_off": float((ctrl > 0).mean()),
               "reference_s": t_ref}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "lower": max(r["program_gap"] for r in rows),
        "upper": min(r["control_gap"] for r in rows),
        "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
