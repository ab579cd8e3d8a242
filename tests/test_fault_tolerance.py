"""Fault-injection suite: deterministic injector/retry units, the archive
writer's sticky-error semantics, and graceful per-field degradation across
all three engines.

The degradation contract is the strong one: the same injected enhancer
failure must yield **byte-identical** conv-only entries from the serial,
batched and streaming engines (the cross-engine bit-identity contract
extends to the failure path), and a degraded field still honors its exact
error bound — the conventional stage alone guarantees it.
"""
import io
import os

import numpy as np
import pytest

import repro
from repro import core, obs, streaming
from repro.core import archive as A
from repro.faults import (DEFAULT, FaultConfig, FaultInjector, InjectedFault,
                          RetryPolicy, degrade_reason, is_degradable, of,
                          retry_with_backoff)
from repro.streaming import pipeline as stream_pipeline
from repro.streaming.writer import AsyncArchiveWriter


def _snapshot(n_fields: int = 3) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(11)
    return {f"f{i}": np.cumsum(rng.standard_normal((3, 8, 8)),
                               axis=0).astype(np.float32)
            for i in range(n_fields)}


# -- injector ----------------------------------------------------------------

def test_injector_fires_at_exact_invocation():
    inj = FaultInjector({"writer.add_entry": 1})
    inj.check("writer.add_entry")               # invocation 0: passes
    with pytest.raises(InjectedFault) as ei:
        inj.check("writer.add_entry")           # invocation 1: fires
    assert ei.value.site == "writer.add_entry"
    assert ei.value.invocation == 1
    inj.check("writer.add_entry")               # invocation 2: healed
    assert inj.count("writer.add_entry") == 3
    assert inj.hits == [("writer.add_entry", 1)]


def test_injector_prefix_matching_and_isolation():
    inj = FaultInjector({"train.*": 0})
    with pytest.raises(InjectedFault):
        inj.check("train.temperature")
    with pytest.raises(InjectedFault):
        inj.check("train.pressure")             # per-site invocation counts
    inj.check("decode.entry")                   # unmatched site: no-op


def test_injector_iterable_plan():
    inj = FaultInjector({"s": [0, 2]})
    for i in range(4):
        if i in (0, 2):
            with pytest.raises(InjectedFault):
                inj.check("s")
        else:
            inj.check("s")


# -- retry -------------------------------------------------------------------

def test_retry_heals_transient_fault():
    inj = FaultInjector({"io": [0, 1]})
    tel = obs.Telemetry()
    sleeps = []

    def fn():
        inj.check("io")
        return "ok"

    out = retry_with_backoff(fn, RetryPolicy(attempts=3, backoff_s=0.01),
                             site="io", tel=tel, sleep=sleeps.append)
    assert out == "ok"
    assert inj.count("io") == 3
    assert sleeps == [0.01, 0.02]               # exponential backoff
    assert tel.counters["faults.retries"] == 2
    assert tel.counters["faults.retries.io"] == 2


def test_retry_exhaustion_reraises_last_error():
    inj = FaultInjector({"io": [0, 1, 2]})
    with pytest.raises(InjectedFault):
        retry_with_backoff(lambda: inj.check("io"),
                           RetryPolicy(attempts=3, backoff_s=0.0),
                           site="io", sleep=lambda s: None)
    assert inj.count("io") == 3                 # exactly `attempts` tries


def test_retry_does_not_catch_nonretryable():
    calls = []

    def fn():
        calls.append(1)
        raise KeyError("not transient")

    with pytest.raises(KeyError):
        retry_with_backoff(fn, RetryPolicy(attempts=5, backoff_s=0.0),
                           sleep=lambda s: None)
    assert len(calls) == 1


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(backoff_s=-1.0)


# -- FaultConfig plumbing ----------------------------------------------------

def test_of_reads_config_attribute():
    fc = FaultConfig(retry=RetryPolicy())
    cfg = core.NeurLZConfig(faults=fc)
    assert of(cfg) is fc
    assert of(core.NeurLZConfig()) is DEFAULT
    assert of(object()) is DEFAULT


@pytest.mark.parametrize("msg,degradable", [
    # Device memory exhausted while running: degrade the field.
    ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
     "allocate 4.00G. That was not possible.", True),
    ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate 1.0G", True),
    # The compiler refused the program or kernel: a bug, never degraded.
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
     "allocating on stack for %lorenzo3d_fwd.1", False),
    ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
     "memory in memory space hbm.", False),
    ("INTERNAL: Mosaic failed to compile TPU kernel: failed to legalize "
     "operation 'func.return'", False),
    ("INTERNAL: something else entirely", False),
])
def test_compile_failures_are_not_degradable(msg, degradable):
    assert is_degradable(RuntimeError(msg)) is degradable


def test_degradability_classification():
    assert is_degradable(InjectedFault("s", 0))
    assert is_degradable(MemoryError())
    assert is_degradable(FloatingPointError())
    assert is_degradable(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert not is_degradable(TypeError("a genuine bug"))
    assert degrade_reason(None) == "non-finite-loss"
    assert degrade_reason(InjectedFault("s", 0)) == "injected"
    assert degrade_reason(MemoryError()) == "error:MemoryError"


def test_fault_config_run_probe_inside_retry():
    """The injection probe sits inside the retried closure, so a transient
    planned fault heals on retry like a real transient error."""
    fc = FaultConfig(injector=FaultInjector({"reader.load": 0}),
                     retry=RetryPolicy(attempts=2, backoff_s=0.0))
    assert fc.run(lambda: 42, site="reader.load") == 42
    # without a retry policy the same plan is fatal
    fc2 = FaultConfig(injector=FaultInjector({"reader.load": 0}))
    with pytest.raises(InjectedFault):
        fc2.run(lambda: 42, site="reader.load")


# -- AsyncArchiveWriter error semantics (regression) -------------------------

def _writer(sink, faults):
    return AsyncArchiveWriter(sink, core.NeurLZConfig(epochs=1),
                              faults=faults, queue_size=2)


def test_writer_failure_is_sticky_and_close_aborts(tmp_path):
    """A failed writer thread must (a) re-raise from every later call with
    the original cause chained, and (b) never seal a footer over the bad
    byte stream — the pre-PR-8 bug cleared the error and finalized."""
    p = os.fspath(tmp_path / "bad.nlz")
    w = _writer(p, FaultConfig(injector=FaultInjector({"writer.add_entry":
                                                       [0, 1, 2, 3]})))
    w.put_entry("a", {"conv": {"blob": b"x" * 16}})
    with pytest.raises(RuntimeError, match="writer thread failed") as ei:
        w.drain()
    assert isinstance(ei.value.__cause__, InjectedFault)
    with pytest.raises(RuntimeError):           # sticky: same failure again
        w.put_entry("b", {"conv": {"blob": b"y"}})
    with pytest.raises(RuntimeError):
        w.close({"field_order": ["a"]})
    # no footer: the container does not open as sealed
    with pytest.raises(A.CorruptArchiveError):
        A.ArchiveReader(p).close()
    scan = A.scan_container(p)
    assert not scan["sealed"] and scan["entries"] == {}


def test_writer_retry_heals_and_leaves_no_torn_bytes(tmp_path):
    p = os.fspath(tmp_path / "healed.nlz")
    inj = FaultInjector({"writer.add_entry": 1})
    w = _writer(p, FaultConfig(injector=inj,
                               retry=RetryPolicy(attempts=3, backoff_s=0.0)))
    w.put_entry("a", {"conv": {"blob": b"x" * 16}})
    w.put_entry("b", {"conv": {"blob": b"y" * 16}})
    stats = w.close({"field_order": ["a", "b"]})
    assert stats["entries"] == 2
    assert inj.hits == [("writer.add_entry", 1)]
    rep = A.verify_container(p)
    assert rep["sealed"] and rep["ok"]
    with A.ArchiveReader(p) as r:
        assert r.read_entry("b")["conv"]["blob"] == b"y" * 16


def test_writer_abort_after_failure_is_clean(tmp_path):
    p = os.fspath(tmp_path / "aborted.nlz")
    w = _writer(p, FaultConfig(injector=FaultInjector({"writer.add_entry":
                                                       0})))
    w.put_entry("a", {"conv": {"blob": b"x"}})
    w.abort()                                   # error path: no footer, no raise
    assert not A.scan_container(p)["sealed"]


# -- graceful degradation across engines -------------------------------------

def _degrade_cfg(engine: str) -> core.NeurLZConfig:
    # fresh injector per run: invocation counts are stateful
    fc = FaultConfig(injector=FaultInjector({"train.f1": 0}))
    return core.NeurLZConfig(epochs=1, mode="strict", engine=engine,
                             group_size=1, faults=fc)


def test_degraded_entries_byte_identical_across_engines():
    fields = _snapshot()
    arcs = {}
    for engine in ("serial", "batched"):
        arcs[engine] = core.compress(fields, rel_eb=1e-3,
                                     config=_degrade_cfg(engine))
    buf = io.BytesIO()
    streaming.compress(fields, buf, 1e-3, config=_degrade_cfg("streaming"))
    buf.seek(0)
    with A.ArchiveReader(buf) as r:
        arcs["streaming"] = core.assemble_streaming_archive(r)

    blobs = {k: A.dumps(v["fields"]) for k, v in arcs.items()}
    assert blobs["serial"] == blobs["batched"] == blobs["streaming"]
    for engine, arc in arcs.items():
        e = arc["fields"]["f1"]
        assert e["degraded"] == "injected", engine
        assert "weights" not in e and e["stats"] == []
        assert "degraded" not in arc["fields"]["f0"]
        assert arc["timing"]["degraded_fields"] == ["f1"], engine


def test_degraded_field_still_honors_error_bound():
    fields = _snapshot()
    cfg = _degrade_cfg("serial")
    arc = core.compress(fields, rel_eb=1e-3, config=cfg)
    dec = core.decompress(arc)
    eb = arc["fields"]["f1"]["abs_eb"]
    err = np.abs(dec["f1"].astype(np.float64)
                 - fields["f1"].astype(np.float64))
    assert float(err.max()) <= eb
    # batched decode path takes the same degraded shortcut
    dec_b = core.decompress(arc, engine="batched")
    np.testing.assert_array_equal(dec_b["f1"], dec["f1"])


def test_degraded_aux_producer_keeps_consumers_identical():
    """A degraded field that feeds another field's cross-channel inputs
    must not perturb the consumer: aux inputs are conventional
    reconstructions, computed from the source regardless of enhancement."""
    fields = _snapshot()
    base = core.NeurLZConfig(epochs=1, mode="strict",
                             cross_field={"f2": ("f1",)})
    clean = core.compress(fields, rel_eb=1e-3, config=base)
    hurt = core.compress(fields, rel_eb=1e-3, config=core.NeurLZConfig(
        epochs=1, mode="strict", cross_field={"f2": ("f1",)},
        faults=FaultConfig(injector=FaultInjector({"train.f1": 0}))))
    assert A.dumps(hurt["fields"]["f2"]) == A.dumps(clean["fields"]["f2"])
    assert hurt["fields"]["f1"]["degraded"] == "injected"


def test_degrade_disabled_raises():
    fields = _snapshot(2)
    cfg = core.NeurLZConfig(epochs=1, faults=FaultConfig(
        injector=FaultInjector({"train.f1": 0}), degrade=False))
    with pytest.raises(InjectedFault):
        core.compress(fields, rel_eb=1e-3, config=cfg)


def test_degradation_counted_on_telemetry():
    tel = obs.Telemetry()
    cfg = core.NeurLZConfig(epochs=1, telemetry=tel, faults=FaultConfig(
        injector=FaultInjector({"train.*": 0})))
    fields = _snapshot(2)
    core.compress(fields, rel_eb=1e-3, config=cfg)
    assert tel.counters["faults.degraded"] == 2


# -- retry sites in the streaming pipeline / decode --------------------------

def test_streaming_reader_load_retry(tmp_path):
    fields = _snapshot(2)
    inj = FaultInjector({"reader.load": 0})
    tel = obs.Telemetry()
    cfg = core.NeurLZConfig(epochs=1, mode="strict", engine="streaming",
                            group_size=1, telemetry=tel,
                            faults=FaultConfig(
                                injector=inj,
                                retry=RetryPolicy(attempts=3,
                                                  backoff_s=0.0)))
    p = os.fspath(tmp_path / "s.nlz")
    streaming.compress(fields, p, 1e-3, config=cfg)
    assert inj.hits == [("reader.load", 0)]
    assert tel.counters["faults.retries.reader.load"] >= 1
    clean = stream_pipeline.compress_dict(fields, 1e-3,
                                    config=core.NeurLZConfig(
                                        epochs=1, mode="strict",
                                        engine="streaming", group_size=1))
    with A.ArchiveReader(p) as r:
        arc = core.assemble_streaming_archive(r)
    assert A.dumps(arc["fields"]) == A.dumps(clean["fields"])


def test_archive_decode_entry_retry(tmp_path):
    fields = _snapshot(2)
    sess = repro.NeurLZ(epochs=1, engine="streaming")
    p = os.fspath(tmp_path / "s.nlz")
    arc = sess.compress_to(fields, p, rel_eb=1e-3)
    want = arc.decode("f0")
    arc.close()
    fc = FaultConfig(injector=FaultInjector({"decode.entry": 0}),
                     retry=RetryPolicy(attempts=3, backoff_s=0.0))
    with repro.Archive.open(p) as arc2:
        arc2.faults = fc
        np.testing.assert_array_equal(arc2.decode("f0"), want)
    assert fc.injector.hits == [("decode.entry", 0)]
    # no retry policy: the injected fault surfaces
    fc2 = FaultConfig(injector=FaultInjector({"decode.entry": 0}))
    with repro.Archive.open(p) as arc3:
        arc3.faults = fc2
        with pytest.raises(InjectedFault):
            arc3.decode("f0")


# -- straggler watchdog ------------------------------------------------------

def test_straggler_watchdog_flags_slow_groups(tmp_path):
    tel = obs.Telemetry()
    cfg = core.NeurLZConfig(epochs=1, mode="strict", engine="streaming",
                            group_size=1, telemetry=tel,
                            faults=FaultConfig(straggler_deadline_s=1e-4))
    report = stream_pipeline.compress_dict(_snapshot(2), 1e-3, config=cfg)
    assert tel.counters.get("faults.stragglers", 0) >= 1
    assert report["timing"]["straggler_overruns"] >= 1


def test_watchdog_disarmed_by_default(tmp_path):
    report = stream_pipeline.compress_dict(
        _snapshot(2), 1e-3,
        config=core.NeurLZConfig(epochs=1, mode="strict",
                                 engine="streaming", group_size=1))
    assert "straggler_overruns" not in report["timing"]
