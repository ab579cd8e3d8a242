"""The readers of the host-phase metrics: device-idle time inside named
program spans, as a share of the traced window."""
import shutil
import time

import pytest

from _tiny import ROOT
from nlzbench import harness, trace_reduce

NEW = ("idle_dataset_pct", "idle_interp_pct", "idle_entropy_pct",
       "idle_enhance_pct")


def _dev(busy):
    """A one-device trace whose device ran exactly ``busy``."""
    dev = trace_reduce.DeviceTrace.__new__(trace_reduce.DeviceTrace)
    dev.ops = {"/device:TPU:0": trace_reduce.merge(busy)}
    dev.modules = {"/device:TPU:0": []}
    return dev


def _run(view):
    cell = harness.find_cell("nyx.compress", harness.load_benchmark(ROOT),
                             ROOT)
    return harness.Run(cell=cell, seed=1, setup_s=1.0, window=(0.0, 1e-7),
                       ops=[harness.OpRecord(i, 0.0, 1.0, True)
                            for i in range(2)],
                       answers=[], trace=view)


def _synthetic(spans, busy=((0, 10), (30, 40))):
    return _run(harness.TraceView(dev=_dev(busy), lo=0.0, hi=100.0,
                                  spans=spans))


def _read(name, run):
    return harness.metric_reader(name, ROOT).read(run)


def test_idle_inside_spans_counts_each_idle_instant_once():
    # Device busy [0, 10] and [30, 40] of a [0, 100] window: idle
    # [10, 30] and [40, 100].  The first dataset span covers half of the
    # first gap and part of the second; the second overlaps the first.
    run = _synthetic([("dataset", 20, 50), ("dataset", 45, 60),
                      ("entropy", 60, 80), ("outliers", 70, 90),
                      ("enhance", 5, 15), ("interp", 150, 160),
                      ("compress", 0, 100)])
    # dataset: idle [20, 30] + [40, 60] = 30 of 100.
    assert _read("idle_dataset_pct", run) == pytest.approx(30.0)
    # entropy u outliers = [60, 90], all idle.
    assert _read("idle_entropy_pct", run) == pytest.approx(30.0)
    # enhance [5, 15]: only [10, 15] is idle.
    assert _read("idle_enhance_pct", run) == pytest.approx(5.0)
    # interp lies wholly outside the window: nothing to read.
    assert _read("idle_interp_pct", run) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_without_their_spans(name):
    """The parent program has the stage spans only."""
    run = _synthetic([("compress", 0, 100), ("conv", 0, 40),
                      ("train", 40, 60), ("finalize", 60, 100),
                      ("op", 0, 100), ("save", 95, 100)])
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None


def test_nested_program_spans_reach_the_readers_and_the_breakdown(tmp_path):
    """A real handle's nested spans, carried onto the recorded v5e trace's
    clock by the harness: the leaf's idle time is read, and the longest
    idle gap is named by the leaf, not by the stage spans around it."""
    import repro
    from test_nlzbench_trace import MARKS, TRACE
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / TRACE.name)
    dev = trace_reduce.DeviceTrace.from_file(str(d / TRACE.name))
    window = (MARKS[0], MARKS[1] + 0.01)
    clock = trace_reduce.ClockMap(zip(
        sorted(s for s, _ in dev.annotations["nlzbench.op"]), MARKS))
    s, e = max(dev.idle_gaps(clock.ns(window[0]), clock.ns(window[1])),
               key=lambda g: g[1] - g[0])

    tel = repro.Telemetry(repro.TelemetryConfig(learning_traces=False))
    with tel.span("compress"), tel.span("train"), tel.span("dataset"):
        time.sleep(0.002)
    leaf = next(sp for sp in tel.spans if sp.name == "dataset")
    assert leaf.dur * 1e9 < (e - s) / 2
    # Centre the leaf on the longest gap.
    mid_s = ((s + e) / 2 - clock.offset_ns) * 1e-9
    perf0 = mid_s - leaf.dur / 2 - leaf.t0

    view = harness.build_trace_view(str(tmp_path), window, list(MARKS), tel,
                                    perf0, [])
    run = _run(view)
    assert _read("idle_dataset_pct", run) == pytest.approx(
        100.0 * leaf.dur / view.window_s, rel=1e-6)
    for name in NEW[1:]:
        assert _read(name, run) is None
    assert harness.breakdown(view)["idle_gaps"][0][0] == "dataset"
