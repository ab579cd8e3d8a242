#!/usr/bin/env python3
"""Time warm compress ops of a configuration at other slice counts, to size
its ``slices`` (a cell's op should take at most half of a 51 s window).

    python3 nlzbench/sizing.py --config nyx --slices 512,64 --ops 2

For each slice count: one cold op (it compiles), then ``--ops`` warm ops on
distinct snapshots, each printed with the program's own conv / train
split.  Exits nonzero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slices", required=True)
    ap.add_argument("--ops", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from nlzbench import device, fields, harness
    from nlzbench.ops import common
    from nlzbench.run import setup_compile_cache
    setup_compile_cache(harness.CACHE_DIR)
    try:
        dev = device.check(1)
    except device.DeviceError as exc:
        print(f"sizing: {exc}", file=sys.stderr)
        return 3
    with open(ROOT / "nlzbench" / "configs" / f"{args.config}.json") as f:
        base = json.load(f)
    rows = []
    for slices in (int(s) for s in args.slices.split(",")):
        cfg = dict(base, slices=slices)
        sess = common.session(cfg)
        for i in range(args.ops + 1):
            t0 = time.perf_counter()
            snap = fields.snapshot(cfg["dataset"], common.shape(cfg),
                                   cfg["fields"], args.seed, index=i,
                                   coupling=cfg["coupling"])
            t1 = time.perf_counter()
            arc = sess.compress(snap, rel_eb=cfg["rel_eb"])
            t2 = time.perf_counter()
            tm = arc["timing"]
            row = {"slices": slices, "op": i, "cold": i == 0,
                   "data_s": t1 - t0, "op_s": t2 - t1,
                   "conv_s": tm["conv_s"], "train_s": tm["train_s"],
                   "degraded": common.degraded(arc)}
            rows.append(row)
            print(json.dumps(row), flush=True)
            del arc, snap
    print(json.dumps({"device": dev, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
