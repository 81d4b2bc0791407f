#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, by a sweep on the
chip, once, to fix the rate its mix file states.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates <r,...>

One process sets the cell up once and offers its mix at each rate in
turn, lowest first, for a warm phase and a window of ``--seconds``,
draining between rates. It stops at the first rate that is not sustained
(``sustained`` states the rule) and prints, last, the highest sustained
rate and the cell's rate, ``CELL_SHARE`` of it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# an open-loop cell offers this share of the highest sustained rate
CELL_SHARE = 0.8
# a sustained rate leaves at most this share of the slots' worth of
# requests waiting for a slot at the close ...
QUEUED_SHARE_OF_SLOTS = 0.25
# ... and gives 95% of the requests due in the window a slot within this
QUEUE_WAIT_P95_MS = 1000.0


def sustained(row: dict, slots: int) -> bool:
    """The backlog did not grow: few requests still wait for a slot at the
    close, and the 95th percentile of the wait for a slot stays short."""
    return (row["queued_at_close"] <= QUEUED_SHARE_OF_SLOTS * slots
            and row["queue_wait_p95_ms"] <= QUEUE_WAIT_P95_MS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    import jax

    from bench import harness, run, window
    from bench.client import CLOCK

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    run._configure_jax()
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    conf, mix = bench.config(cell), bench.mix(cell)
    serving = run.Setup(bench.family(conf), cell["config"], conf, args.seed,
                        CLOCK)
    eng = serving.ex.engine
    best = None
    for k, rate in enumerate(sorted(float(r) for r in args.rates.split(","))):
        m = dict(mix, rate_per_s=rate)
        traffic = bench.generator(m).make(m, args.seed + k, args.seconds,
                                          conf["model"]["vocab_size"])
        base = (k + 1) * 1_000_000
        traffic.items = [dataclasses.replace(it, rid=base + it.rid)
                         for it in traffic.items]
        client, _ = run._serve(serving, traffic, args.seconds, None,
                            run.CompileCounter())
        recs, _ = client.relative()
        close = args.seconds
        due = window.due_in(recs, close)
        done = [r for r in due if r.done is not None and r.done <= close]
        row = {
            "rate": rate, "offered": len(due), "finished": len(done),
            "queued_at_close": len(eng.queue),
            "ttft_p95_ms": window.p95(window.ttft_ms(recs, close)),
            "queue_wait_p95_ms": window.p95(window.queue_wait_ms(recs,
                                                                 close)),
            "itl_p95_ms": window.p95(window.itl_ms(recs, close)),
            "output_tok_s": window.tokens_in(recs, close) / close,
        }
        row["sustained"] = sustained(row, conf["engine"]["slots"])
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        best = rate
        while client.busy():
            serving.ex.step()
    serving.close()
    print(json.dumps({"highest_sustained_rate": best,
                      "cell_rate": None if best is None
                      else round(CELL_SHARE * best, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
