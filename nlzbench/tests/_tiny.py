"""Cells of the committed benchmark at sizes a CPU test run can hold.

The enhancer's gain over the conventional stage alone depends on the size,
so the tiny cell states its own least gain: the CPU readings it was set
from are in ``PERF.md`` (section 2)."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SIZES = {"nyx.compress": {"slices": 32, "plane": [64, 64],
                          "checks": {"enhancer_gain_db": 0.5}},
         "hurricane.decode": {"slices": 100, "plane": [36, 36],
                              "checks": {"enhancer_gain_db": -0.6,
                                         "replay_err_over_eb": 1e-4}}}


def tiny_cell(name: str):
    from nlzbench import harness
    cell = harness.find_cell(name, harness.load_benchmark(ROOT), ROOT)
    return dataclasses.replace(cell, config=dict(cell.config, **SIZES[name]))
