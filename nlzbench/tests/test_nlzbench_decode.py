"""The decode cell on the CPU: the plain reference's forward against the
program's, a sound run of the tiny cell, and runs with the decode broken
underneath, each caught by the number that should catch it.

Each run skips only the look for a chip (``platform="cpu"``) and drives the
rest of a cell's run: data, set-up compress, window, read-back, replay and
comparison."""
import contextlib
import dataclasses
import time

import numpy as np
import pytest

from _tiny import tiny_cell

from nlzbench import control, harness, reference, trace_reduce
from nlzbench.ops import common

SEED = 2 ** 31 + 91
CELL = "hurricane.decode"
# The reference and the program compute the same float32 network in
# different orders (XLA convolutions at HIGHEST against multiply-and-sum
# taps), ten layers of sums over up to 9 x 16 products: their outputs, in
# (-1, 1), read at most ~4e-7 apart on these seeds.  The tolerance leaves
# 25x room; bfloat16 contractions miss it by 700x (~7e-3).
TOL = 1e-5


def test_relaxed_regulation_states_twice_the_bound():
    assert common.MODE_LIMIT == {"strict": 1.0, "relaxed": 2.0}
    assert common.error_limit(tiny_cell(CELL).config) == 2.0


def _net(seed):
    """Seeded weights with nonzero biases and inputs at ``[3, 36, 36]``,
    ``c_in`` 2: the plane pads to 48 and crops back."""
    import jax
    import jax.numpy as jnp
    from repro.core import skipping_dnn
    cfg = skipping_dnn.SkippingDNNConfig(c_in=2)
    params = skipping_dnn.init_params(jax.random.PRNGKey(seed), cfg)
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                               a.shape, a.dtype), params)
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (3, 36, 36, 2),
                          jnp.float32)
    return params, x


def _reference(params, x):
    import jax
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, x))


@pytest.mark.parametrize("seed", [3, 11])
def test_reference_forward_matches_the_program(seed):
    from repro.core import skipping_dnn
    params, x = _net(seed)
    got = np.asarray(skipping_dnn.forward(params, x))[..., 0]
    assert got.shape == (3, 36, 36)
    assert np.abs(got - _reference(params, x)).max() <= TOL
    z = np.asarray(skipping_dnn.forward(params, x, regulated=False))[..., 0]
    import jax
    with jax.default_matmul_precision("highest"):
        zr = np.asarray(reference.forward(params, x, regulated=False))
    assert np.abs(z - zr).max() <= 2 * TOL * max(1.0, np.abs(zr).max())


def test_bf16_contraction_breaks_the_tolerance(monkeypatch):
    """The program's forward with its channel contraction fed in bfloat16
    (``control.dot_bf16``), traced afresh outside ``jit``."""
    from repro.core import skipping_dnn
    params, x = _net(3)
    monkeypatch.setattr(skipping_dnn, "_dot", control.dot_bf16)
    got = np.asarray(skipping_dnn._forward_core(
        params, x, regulated=True, skip=True, conv=skipping_dnn._conv,
        deconv=skipping_dnn._deconv))[..., 0]
    assert np.abs(got - _reference(params, x)).max() > 100 * TOL


def test_layer_shapes_are_the_programs():
    import jax
    from repro.core import skipping_dnn
    for c_in, skip in ((1, True), (2, True), (3, False)):
        cfg = skipping_dnn.SkippingDNNConfig(c_in=c_in, skip=skip)
        params = skipping_dnn.init_params(jax.random.PRNGKey(0), cfg)
        want = {n: p["w"].shape for n, p in params.items()}
        assert reference.layer_shapes(c_in, cfg.widths, skip) == want


def run(cell=None, seconds=0.5):
    return harness.run_cell(cell or tiny_cell(CELL), seed=SEED,
                            seconds=seconds, trace=False,
                            t_process0=time.perf_counter(), platform="cpu")


def test_sound_decode_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"decode_MBps", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"max_err_over_eb", "enhancer_gain_db",
                                  "replay_err_over_eb", "unanswered"}


@contextlib.contextmanager
def _bf16_inference():
    """The decode's inference programs traced again with the contraction
    in bfloat16 (only they: the rest of the process keeps its programs)."""
    from repro.core import online_trainer, skipping_dnn
    orig = skipping_dnn._dot
    skipping_dnn._dot = control.dot_bf16
    online_trainer._predict.clear_cache()
    skipping_dnn._forward_fast.clear_cache()
    try:
        yield
    finally:
        skipping_dnn._dot = orig
        online_trainer._predict.clear_cache()
        skipping_dnn._forward_fast.clear_cache()


def _decode_fault(kind):
    """``Archive.decode`` with the answer broken where it is produced."""
    from repro.core import archive_api, neurlz
    orig = archive_api.Archive.decode
    first = {}

    def broken(self, name, roi=None):
        if kind == "dnn_bf16":
            with _bf16_inference():
                return orig(self, name, roi)
        y = orig(self, name, roi)
        if kind == "answer_altered":
            y = np.array(y, copy=True)
            y.flat[0] += 10 * 1e-3 * float(y.max() - y.min())
        elif kind == "half_slices":
            y = y[: y.shape[0] // 2]
        elif kind == "stale":       # every snapshot answered by the first
            y = first.setdefault(name, y)
        return y

    if kind == "conv_only":
        return neurlz, "decode_field_entry", lambda e, rec, aux, ax: rec
    return archive_api.Archive, "decode", broken


@pytest.mark.parametrize("kind, caught_by", [
    ("answer_altered", "max_err_over_eb"),
    ("stale", "max_err_over_eb"),
    ("half_slices", "max_err_over_eb"),
    ("dnn_bf16", "replay_err_over_eb"),
    ("conv_only", "replay_err_over_eb"),
])
def test_broken_decode_is_not_correct(monkeypatch, kind, caught_by):
    monkeypatch.setattr(*_decode_fault(kind))
    res = run(seconds=2.0 if kind == "stale" else 0.5)
    assert not res["correct"], (kind, res["checks"])
    assert not harness.passes(res["checks"][caught_by]), res["checks"]


def test_untrained_enhancer_is_not_correct():
    cell = tiny_cell(CELL)
    cell = dataclasses.replace(cell, config=dict(cell.config, epochs=0))
    res = run(cell)
    assert not res["correct"]
    assert not harness.passes(res["checks"]["enhancer_gain_db"])
    assert harness.passes(res["checks"]["replay_err_over_eb"])


# ---------------------------------------------------------------------------
# The decode cell's metric readers on synthetic runs
# ---------------------------------------------------------------------------

def _run_with(view, cell_name=CELL, ops=2):
    cell = tiny_cell(cell_name)
    recs = [harness.OpRecord(i, 0.0, 1.0, True, field_bytes=10 ** 6,
                             values=cell.config["slices"] * 36 * 36,
                             info={"snapshot": 0, "field": "cloud"})
            for i in range(ops)]
    return harness.Run(cell=cell, seed=1, setup_s=1.0, window=(0.0, 4.0),
                       ops=recs, answers=[], trace=view,
                       peaks={"flops": 1e12, "hbm_bytes_per_s": 1e9})


def _view(modules):
    dev = trace_reduce.DeviceTrace.__new__(trace_reduce.DeviceTrace)
    dev.ops = {"/device:TPU:0": trace_reduce.merge((s, e)
                                                   for _, s, e in modules)}
    dev.modules = {"/device:TPU:0": list(modules)}
    return harness.TraceView(dev=dev, lo=0.0, hi=4e9, spans=[])


def _read(name, run):
    from _tiny import ROOT
    return harness.metric_reader(name, ROOT).read(run)


def test_decode_readers():
    from nlzbench import flops
    run = _run_with(_view([("jit_predict_graph", 0.0, 1e9),
                           ("jit_predict_graph", 2e9, 2.5e9),
                           ("jit__predict_group", 3e9, 4e9)]))
    assert _read("decode_MBps", run) == pytest.approx(0.5)
    assert _read("device_idle_pct.decode", run) == pytest.approx(37.5)
    cfg = run.cell.config
    fwd = cfg["slices"] * flops.dnn_forward_flops(36, 36, 2)
    assert _read("mfu_pct.decode", run) == pytest.approx(
        100.0 * 2 * fwd / (4.0 * 1e12))
    least, _ = flops.least_time(2 * fwd, 2 * cfg["slices"]
                                * flops.dnn_forward_bytes(36, 36, 2),
                                run.peaks)
    # Only the single-field inference program counts: 1.5 s.
    assert _read("infer_roofline", run) == pytest.approx(100 * least / 1.5)


@pytest.mark.parametrize("name", ["decode_MBps", "device_idle_pct.decode",
                                  "mfu_pct.decode", "infer_roofline"])
def test_decode_readers_are_silent_elsewhere(name):
    """Nothing to read in the compress cell, and no inference program in a
    trace that ran none."""
    view = _view([("jit__train_group_fused", 0.0, 1e9)])
    assert _read(name, _run_with(view, "nyx.compress")) is None
    if name == "infer_roofline":
        assert _read(name, _run_with(view)) is None
