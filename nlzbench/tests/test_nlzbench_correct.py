"""``correct`` on the CPU at sizes a test run can hold: sound runs pass,
runs with the timed path broken underneath fail, and so do the controls.

Each run skips only the look for a chip (``platform="cpu"``) and drives the
rest of a cell's run: data, set-up, window, read-back and comparison."""
import time

import numpy as np
import pytest

from _tiny import tiny_cell

from nlzbench import control, harness

SEED = 2 ** 31 + 77
CELL = "nyx.compress"


def run(name=CELL, seconds=0.5):
    return harness.run_cell(tiny_cell(name), seed=SEED, seconds=seconds,
                            trace=False, t_process0=time.perf_counter(),
                            platform="cpu")


def test_sound_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    want = {m["name"] for m in tiny_cell(CELL).end_to_end}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"max_err_over_eb", "enhancer_gain_db",
                                  "unanswered"}


def _bump(y, rel=1e-3, by=10.0):
    """One value moved by ``by`` bounds."""
    y = np.array(y, copy=True)
    y.flat[0] += by * rel * float(y.max() - y.min())
    return y


def _compress_fault(kind):
    from repro.api import NeurLZ
    orig = NeurLZ.compress
    memo = {}

    def altered(self, fields, bounds=None, **kw):
        if kind == "answer_altered":
            name = next(iter(fields))
            fields = dict(fields, **{name: _bump(fields[name])})
        elif kind == "half_batch":
            fields = {k: v[: v.shape[0] // 2] for k, v in fields.items()}
        elif kind == "state_unchanged":
            if "arc" not in memo:
                memo["arc"] = orig(self, fields, bounds, **kw)
            return memo["arc"]
        return orig(self, fields, bounds, **kw)
    return NeurLZ, "compress", altered


@pytest.mark.parametrize("kind", ["answer_altered", "half_batch",
                                  "state_unchanged"])
def test_broken_timed_path_is_not_correct(monkeypatch, kind):
    monkeypatch.setattr(*_compress_fault(kind))
    res = run(seconds=0.5)
    assert not res["correct"], (kind, res["checks"])


def test_decode_that_skips_the_enhancer_is_not_correct(monkeypatch):
    """The conventional reconstruction keeps the bound on its own, so only
    the enhancer's gain can tell a decode that skips inference."""
    from repro.compressors import registry
    from repro.core.archive_api import Archive

    def conv_only(self, **_):
        return registry.decompress_many(
            {n: self.entry(n)["conv"] for n in self.field_names})

    monkeypatch.setattr(Archive, "decode_all", conv_only)
    res = run(seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["max_err_over_eb"]["value"] <= 1.0
    assert res["checks"]["enhancer_gain_db"]["value"] == 0.0


@pytest.mark.parametrize("which", ["reference_bf16", "dnn_bf16",
                                   "conv_only"])
def test_controls_are_not_correct(tmp_path, which):
    cell = tiny_cell(CELL)
    out = control.reading(cell, which, SEED, control.pool_snapshot(cell, 1),
                          1, str(tmp_path))
    assert not out["correct"], out["checks"]


def test_sound_reading_is_correct(tmp_path):
    cell = tiny_cell(CELL)
    out = control.reading(cell, "none", SEED, control.pool_snapshot(cell, 1),
                          1, str(tmp_path))
    assert out["correct"], out["checks"]
    assert 0 < out["bits_per_value"] < 32
