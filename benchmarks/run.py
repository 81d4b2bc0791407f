"""Benchmark entry point: one bench per paper claim + the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--skip-roofline]

Prints ``name,value,unit,detail`` CSV rows per claim bench, then the
roofline tables derived from results/dryrun (if the dry-run has been run).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.core import recompile


def main() -> None:
    recompile.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter on bench names")
    args = ap.parse_args()

    from benchmarks import paper_claims

    failures = 0
    print("name,value,unit,detail")
    for bench in paper_claims.ALL:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            for name, value, unit, detail in bench():
                print(f"{name},{value:.6g},{unit},{detail}")
        except Exception as e:  # a failing bench must not hide the others
            failures += 1
            print(f"{bench.__name__},ERROR,,{type(e).__name__}: {e}",
                  file=sys.stdout)
            traceback.print_exc(file=sys.stderr)

    if not args.skip_roofline:
        from benchmarks import roofline

        roofline.main()

    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
