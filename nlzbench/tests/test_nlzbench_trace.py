"""Trace reduction on a trace recorded on one TPU v5e by
``nlzbench/tests/record_trace.py``: two annotated host spans
``nlzbench.op``, each around one jitted program, 50 ms apart."""
from pathlib import Path

import pytest

from _tiny import ROOT  # noqa: F401  (puts the repo on sys.path)
from nlzbench import trace_reduce

TRACE = Path(__file__).parent / "data" / "v5e_two_programs.xplane.pb"
# perf_counter seconds the recorder printed just before each annotation.
MARKS = (25.584006656, 25.635255872)
# The device plane's clock runs about 1.2 ms behind the host plane's in this
# trace: a program can appear to start before the span that launched it.
SKEW_NS = 5e6


def window(dev):
    ann = dev.annotations["nlzbench.op"]
    return (min(s for s, _ in ann) - SKEW_NS, max(e for _, e in ann) + SKEW_NS)


@pytest.fixture(scope="module")
def dev():
    return trace_reduce.DeviceTrace.from_file(str(TRACE))


def test_device_planes_and_programs(dev):
    assert dev.devices == ["/device:TPU:0"]
    mods = dev.modules["/device:TPU:0"]
    assert [n for n, _, _ in mods] == ["jit__lambda", "jit__lambda"]
    assert len(dev.annotations["nlzbench.op"]) == 2


def test_busy_union_and_idle(dev):
    lo, hi = window(dev)
    busy = dev.busy_ns(lo, hi)
    ops = dev.op_events["/device:TPU:0"]
    # Busy is the union of the op intervals: at most their sum, at least
    # the longest op, and it lies inside the programs' executions.
    assert max(e - s for _, s, e in ops) <= busy <= sum(e - s
                                                        for _, s, e in ops)
    progs = dev.module_intervals(("jit__lambda",), lo, hi)
    assert len(progs) == 2
    assert busy <= trace_reduce.length(progs)
    gaps = dev.idle_gaps(lo, hi)
    assert trace_reduce.length(gaps) + busy == pytest.approx(hi - lo)
    # The 50 ms sleep between the programs is the longest gap.
    assert max(e - s for s, e in gaps) > 45e6


def test_clock_alignment_puts_programs_inside_their_spans(dev):
    starts = sorted(s for s, _ in dev.annotations["nlzbench.op"])
    clock = trace_reduce.ClockMap(zip(starts, MARKS))
    for (s, e), mark in zip(sorted(dev.annotations["nlzbench.op"]), MARKS):
        assert clock.ns(mark) == pytest.approx(s, abs=2e5)
    ann = sorted(dev.annotations["nlzbench.op"])
    mods = sorted(dev.modules["/device:TPU:0"], key=lambda m: m[1])
    for (a0, a1), (_, m0, m1) in zip(ann, mods):
        assert a0 - SKEW_NS <= m0 and m1 <= a1 + SKEW_NS


def test_top_modules_and_gap_attribution(dev):
    lo, hi = window(dev)
    top = dev.top_modules(lo, hi)
    assert [n for n, _ in top] == ["jit__lambda"]
    assert top[0][1] == pytest.approx(trace_reduce.length(
        dev.module_intervals(("jit__lambda",), lo, hi)) * 1e-9)
    spans = [("op", s, e) for s, e in dev.annotations["nlzbench.op"]]
    spans.append(("window", lo, hi))
    named = trace_reduce.attribute(dev.idle_gaps(lo, hi), spans, k=3)
    assert named[0][0] == "window" and named[0][1] > 0.045


@pytest.mark.parametrize("a,b,want", [
    ([(0, 2), (1, 3), (5, 6)], None, [(0, 3), (5, 6)]),
    ([(0, 3), (5, 6)], [(1, 5.5)], [(1, 3), (5, 5.5)]),
    ([(4, 4), (1, 2)], None, [(1, 2)]),
])
def test_interval_arithmetic(a, b, want):
    got = trace_reduce.merge(a) if b is None else \
        trace_reduce.intersect(trace_reduce.merge(a), trace_reduce.merge(b))
    assert got == want
