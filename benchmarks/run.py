"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/README of record:
EXPERIMENTS.md maps each prefix to the paper table/figure it reproduces).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

MODULES = [
    "bench_error_validation",   # Fig 11 / Fig 5
    "bench_rate_psnr",          # Fig 10
    "bench_bitrate_reduction",  # Table 2
    "bench_scalability",        # Table 3
    "bench_ablations",          # Fig 4
    "bench_training_evolution", # Figs 7/12/16
    "bench_regulation",         # Fig 13 / §5.1
    "bench_conflict",           # Fig 17 / §5.3
    "bench_grad_compress",      # framework integration (DESIGN.md §4)
    "bench_kernels",            # Pallas kernel validation
    "bench_roofline",           # §Roofline table from dry-run records
    "bench_streaming",          # bounded-memory pipeline vs in-memory engine
    "bench_obs",                # telemetry overhead guard + Perfetto trace
    "bench_durability",         # NLZSTRM2 checksum cost + salvage scan
    "bench_serving",            # serving tier: cache, coalesce, transcode
]


# CI smoke subset: the kernel validations plus the engine-comparison rows of
# the scalability bench and the streaming-budget row, at tiny-field settings
# (see each module's smoke path).
MODULES_SMOKE = [
    "bench_kernels",
    "bench_roofline",
    "bench_scalability",
    "bench_streaming",
    "bench_obs",
    "bench_durability",
    "bench_serving",
]

# Committed perf ledger (repo root): the smoke profile's machine-readable
# run record; scripts/perf_summary.py --compare diffs two of these and
# fails on >25% wall-clock regression.
LEDGER = "BENCH_PR10.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-field CI profile (fast, regression-only)")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark module")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="write the run's rows as JSON here (--smoke "
                         f"defaults to <repo-root>/{LEDGER})")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.setup()

    failures = 0
    ran = 0
    modules = MODULES_SMOKE if args.smoke else MODULES
    for name in modules:
        if args.only and args.only not in name:
            continue
        ran += 1
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            import inspect
            kwargs = {"full": args.full}
            if "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = args.smoke
            mod.run(**kwargs)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if args.only and ran == 0:
        print(f"# --only {args.only!r} matched no module in "
              f"{modules}", file=sys.stderr)
        sys.exit(2)
    ledger = args.ledger
    if ledger is None and args.smoke and not args.only:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ledger = os.path.join(root, LEDGER)
    if ledger:
        from . import common
        with open(ledger, "w") as f:
            json.dump({"profile": "smoke" if args.smoke else
                       ("full" if args.full else "default"),
                       "modules": modules, "failures": failures,
                       "rows": common.ROWS}, f, indent=1, default=str)
            f.write("\n")
        print(f"# ledger -> {ledger} ({len(common.ROWS)} rows)", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
