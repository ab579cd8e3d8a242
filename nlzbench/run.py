#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 nlzbench/run.py --workload nyx.compress --seed 7 --seconds 40 \
        --trace 0

The cell, its configuration, its traffic and its metrics are found by name
from ``BENCHMARK.json`` (see ``nlzbench/harness.py``).  The last line of
standard output is the result object; the numbers compared for
``correct`` are printed with their limits as the last lines of standard
error and under the result's last key, ``checks``.  Exit codes: 0 a result
was printed, 2 the checkout holds no ``repro`` package or the cell is
malformed, 3 no accelerator or too few chips (no result line either way).
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_compile_cache(cache_dir: Path) -> None:
    """JAX's persistent cache at the benchmark's fixed directory, for every
    compile including the eager ops' (no minimum compile time)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def finite(obj):
    """The result with every infinite number written as the largest float
    of its sign, so the line stays strict JSON (an infinite error reads as
    1.8e308, still above every limit; a gain of minus infinity as -1.8e308,
    still below)."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None if obj != obj else math.copysign(sys.float_info.max, obj)
    return obj


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"nlzbench: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from nlzbench import device, harness
    try:
        cell = harness.find_cell(args.workload, harness.load_benchmark(ROOT),
                                 ROOT)
    except (harness.CellError, OSError, KeyError) as exc:
        print(f"nlzbench: {exc}", file=sys.stderr)
        return 2
    # The TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes nothing outside its checkout and its TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    setup_compile_cache(harness.CACHE_DIR)
    try:
        result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace),
                                  t_process0=T_PROCESS0)
    except device.DeviceError as exc:
        print(f"nlzbench: {exc}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"[check] {name} = {c['value']!r} "
              f"(limit {c.get('pass_if', '<=')} {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
