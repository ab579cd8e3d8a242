"""Telemetry subsystem: span trees, counters/gauges, learning traces,
exporters, and the cross-engine timing schema.

Acceptance contract (PR 7): a 3-field snapshot with telemetry enabled on
each engine produces (a) a span tree whose conv/train/write spans nest
correctly and sum to within 10% of ``total_s``, (b) per-field per-epoch
learning traces, (c) valid Chrome ``trace_event`` JSON whose streaming
reader/writer threads overlap compute — and telemetry *disabled* produces
byte-identical archives.
"""
import io
import json

import numpy as np
import pytest

from repro import obs
from repro.core import archive as A
from repro.core import neurlz

ENGINES = ("serial", "batched", "streaming")
EPOCHS = 2

_rng = np.random.default_rng(3)
FIELDS = {f"f{i}": _rng.normal(size=(6, 12, 12)).astype(np.float32)
          for i in range(3)}


def _run(engine, telemetry=None, **kw):
    cfg = neurlz.NeurLZConfig(engine=engine, epochs=EPOCHS,
                              telemetry=telemetry, **kw)
    return neurlz.compress_impl(FIELDS, 1e-3, config=cfg)


@pytest.fixture(scope="module")
def runs():
    """Per engine: (telemetry handle, traced archive, untraced archive)."""
    out = {}
    for engine in ENGINES:
        tel = obs.Telemetry()
        out[engine] = (tel, _run(engine, telemetry=tel), _run(engine))
    return out


def _root(tel):
    roots = [s for s in tel.spans if s.name == "compress"]
    assert len(roots) == 1
    return roots[0]


# ---------------------------------------------------------------------------
# Span tree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_span_tree_nests_under_root(runs, engine):
    tel, _, _ = runs[engine]
    root = _root(tel)
    assert root.parent is None
    ids = {s.id for s in tel.spans}
    for s in tel.spans:
        if s is not root:
            assert s.parent in ids, f"orphan span {s.name}"
    # conv and train happen under the root (directly or via a parent chain)
    by_id = {s.id: s for s in tel.spans}

    def ancestor_of_root(s):
        while s.parent is not None:
            s = by_id[s.parent]
        return s is root

    names = {s.name for s in tel.spans}
    assert {"conv", "train"} <= names
    assert all(ancestor_of_root(s) for s in tel.spans if s is not root)


@pytest.mark.parametrize("engine", ENGINES)
def test_spans_sum_to_root_within_10pct(runs, engine):
    tel, arc, _ = runs[engine]
    root = _root(tel)
    kids = [s for s in tel.spans
            if s.parent == root.id and s.thread == root.thread]
    covered = sum(s.dur for s in kids)
    assert covered >= 0.9 * root.dur, (
        f"{engine}: top-level spans cover {covered:.3f}s of root "
        f"{root.dur:.3f}s")
    assert covered <= root.dur * 1.01
    # the root tracks the engine's own total_s stopwatch
    assert root.dur == pytest.approx(arc["timing"]["total_s"], rel=0.25,
                                     abs=0.25)


def test_streaming_spans_cover_all_threads(runs):
    tel, _, _ = runs["streaming"]
    threads = {s.thread_name for s in tel.spans}
    assert any("writer" in t for t in threads), threads
    assert any("reader" in t for t in threads), threads
    # orphan-thread spans (reader/writer) parent to the root span
    root = _root(tel)
    for s in tel.spans:
        if s.thread != root.thread:
            assert s.parent == root.id


def test_streaming_writer_overlaps_compute(runs):
    tel, _, _ = runs["streaming"]
    root = _root(tel)
    main = [s for s in tel.spans
            if s.thread == root.thread and s is not root]
    other = [s for s in tel.spans if s.thread != root.thread]
    assert other, "no reader/writer-thread spans recorded"

    def overlaps(a, b):
        return a.t0 < b.t0 + b.dur and b.t0 < a.t0 + a.dur

    assert any(overlaps(o, m) for o in other for m in main), (
        "async-thread spans never overlapped main-thread compute")


# ---------------------------------------------------------------------------
# Learning traces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_learning_traces_one_record_per_epoch(runs, engine):
    tel, _, _ = runs[engine]
    assert sorted(tel.traces) == sorted(FIELDS)
    for name in FIELDS:
        recs = tel.trace(name)
        assert len(recs) == EPOCHS
        assert [r["epoch"] for r in recs] == list(range(EPOCHS))
        for r in recs:
            assert {"loss", "residual_rms", "pred_psnr",
                    "pred_outlier_rate", "pred_bitrate"} <= set(r)
            assert r["loss"] >= 0.0
            assert 0.0 <= r["pred_outlier_rate"] <= 1.0
            assert r["pred_bitrate"] > 0.0


def test_sample_psnr_traces_measured_quality():
    tel = obs.Telemetry(obs.TelemetryConfig(sample_psnr=True,
                                            sample_slices=2))
    _run("serial", telemetry=tel)
    for name in FIELDS:
        recs = tel.trace(name)
        assert all("sample_psnr" in r for r in recs)
        assert all(np.isfinite(r["sample_psnr"]) for r in recs)


def test_sample_psnr_does_not_change_archive():
    tel = obs.Telemetry(obs.TelemetryConfig(sample_psnr=True))
    arc = _run("serial", telemetry=tel)
    arc0 = _run("serial")
    assert A.dumps(arc["fields"]) == A.dumps(arc0["fields"])


# ---------------------------------------------------------------------------
# Disabled path: byte identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_telemetry_disabled_archives_byte_identical(runs, engine):
    _, arc_on, arc_off = runs[engine]
    assert A.dumps(arc_on["fields"]) == A.dumps(arc_off["fields"])


# ---------------------------------------------------------------------------
# Counters / gauges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_conv_counters_match_conv_stage_stats(runs, engine):
    tel, arc, _ = runs[engine]
    cs = arc["timing"]["conv_stage"]
    c = tel.counters
    assert c.get("conv.dispatches", 0) == cs["calls"]
    assert c.get("conv.groups", 0) == cs["groups"]
    assert c.get("conv.batched_fields", 0) == cs["batched_fields"]
    assert c.get("conv.fallback_fields", 0) == cs["fallback_fields"]


def test_streaming_ledger_gauge_and_writer_counters(runs):
    tel, arc, _ = runs["streaming"]
    g = tel.gauges
    assert g["stream.resident_bytes"]["max"] == \
        arc["timing"]["peak_resident_bytes"]
    assert tel.counters["writer.entries"] == len(FIELDS)
    assert tel.counters["stream.evictions"] > 0
    assert "writer.queue_depth" in g


def test_archive_decode_counts_entry_reads(tmp_path):
    from repro.core import archive_api
    from repro.streaming import pipeline
    path = str(tmp_path / "snap.nlzs")
    pipeline.compress(FIELDS, path, 1e-3,
                      config=neurlz.NeurLZConfig(engine="streaming",
                                                 epochs=EPOCHS))
    tel = obs.Telemetry()
    with archive_api.Archive.open(path) as arc:
        arc.telemetry = tel
        arc.decode("f1")
        assert tel.counters["archive.entry_reads"] == \
            len(arc.reader.entry_reads)
        assert tel.counters["archive.entry_reads"] >= 1
        assert any(s.name == "decode" and s.attrs.get("field") == "f1"
                   for s in tel.spans)


# ---------------------------------------------------------------------------
# Cross-engine timing schema (satellite: timing inconsistency fix)
# ---------------------------------------------------------------------------

def test_timing_schema_keys_equal_across_engines(runs):
    keysets = {e: set(runs[e][2]["timing"]) for e in ENGINES}
    for e in ENGINES:
        assert set(obs.TIMING_KEYS) <= keysets[e], e
    assert keysets["serial"] == keysets["batched"]
    # streaming reports the same core schema plus its ledger/writer extras
    assert keysets["serial"] <= keysets["streaming"]


@pytest.mark.parametrize("engine", ENGINES)
def test_enabled_timing_carries_span_summary(runs, engine):
    _, arc, arc_off = runs[engine]
    assert "spans" in arc["timing"]
    assert "spans" not in arc_off["timing"]
    spans = arc["timing"]["spans"]
    assert {"conv", "train"} <= set(spans)
    for agg in spans.values():
        assert agg["count"] >= 1 and agg["wall_s"] >= 0.0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_chrome_trace_is_valid_trace_event_json(runs):
    tel, _, _ = runs["streaming"]
    doc = json.loads(json.dumps(tel.chrome_trace(), default=float))
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no complete events"
    for e in xs:
        assert {"name", "cat", "pid", "tid", "ts", "dur"} <= set(e)
    assert len({e["tid"] for e in xs}) >= 3   # main + reader + writer
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any("writer" in n for n in names)
    # gauge sample trails export as counter tracks
    assert any(e["ph"] == "C" for e in events)


def test_jsonl_export_round_trips(runs):
    tel, _, _ = runs["serial"]
    buf = io.StringIO()
    n = tel.export_jsonl(buf)
    lines = buf.getvalue().splitlines()
    assert len(lines) == n
    recs = [json.loads(ln) for ln in lines]
    assert recs[0]["type"] == "meta"
    kinds = {r["type"] for r in recs}
    assert {"span", "counter", "learning_trace"} <= kinds
    trace_lines = [r for r in recs if r["type"] == "learning_trace"]
    assert len(trace_lines) == len(FIELDS) * EPOCHS


def test_summary_aggregates(runs):
    tel, _, _ = runs["batched"]
    s = tel.summary()
    assert sorted(s["fields"]) == sorted(FIELDS)
    assert s["epochs"] == {n: EPOCHS for n in FIELDS}
    assert s["dropped_spans"] == 0
    assert s["spans"]["compress"]["count"] == 1


# ---------------------------------------------------------------------------
# Handle mechanics
# ---------------------------------------------------------------------------

def test_span_cap_drops_not_grows():
    tel = obs.Telemetry(obs.TelemetryConfig(max_spans=3))
    for i in range(10):
        with tel.span("s", i=i):
            pass
    assert len(tel.spans) == 3
    assert tel.dropped_spans == 7


def test_perf0_puts_spans_on_the_perf_counter_clock():
    import time
    tel = obs.Telemetry()
    before = time.perf_counter()
    with tel.span("s"):
        pass
    after = time.perf_counter()
    (sp,) = tel.spans
    assert before <= tel.perf0 + sp.t0 <= tel.perf0 + sp.t0 + sp.dur <= after
    with pytest.raises(AttributeError):
        tel.perf0 = 0.0


def test_of_maps_none_to_null():
    cfg = neurlz.NeurLZConfig()
    assert obs.of(cfg) is obs.NULL
    tel = obs.Telemetry()
    cfg = neurlz.NeurLZConfig(telemetry=tel)
    assert obs.of(cfg) is tel


def test_session_api_threads_telemetry(tmp_path):
    import repro
    tel = repro.Telemetry()
    sess = repro.NeurLZ(engine="batched", epochs=EPOCHS, telemetry=tel)
    arc = sess.compress(FIELDS, rel_eb=1e-3)
    assert arc.telemetry is tel
    assert {s.name for s in tel.spans} >= {"compress", "conv", "train"}
    # streaming compress_to attaches the same handle to the lazy Archive
    tel2 = repro.Telemetry()
    sess2 = sess.replace(telemetry=tel2)
    path = str(tmp_path / "s.nlzs")
    with sess2.compress_to(FIELDS, path, rel_eb=1e-3) as arc2:
        assert arc2.telemetry is tel2
        arc2.decode("f0")
        assert tel2.counters["archive.entry_reads"] >= 1


# ---------------------------------------------------------------------------
# Host phases: leaf spans and host<->device transfer counters
# ---------------------------------------------------------------------------

# Leaf span -> the stage span it nests in.  The streaming engine shares the
# conventional stage and the batched engine's prepare/results helpers; its
# ``retire`` takes the place of ``finalize``.
PHASES = {
    "batched": {"dataset": "train", "upload": "train", "dispatch": "train",
                "wait": "finalize", "fetch": "finalize",
                "enhance": "finalize", "outliers": "finalize",
                "pack": "finalize", "interp": "conv", "entropy": "conv"},
    "streaming": {"dataset": "train", "upload": "train", "wait": "retire",
                  "fetch": "retire", "interp": "conv", "entropy": "conv"},
}


@pytest.mark.parametrize("engine", sorted(PHASES))
def test_phase_spans_nest_in_their_stage(runs, engine):
    tel, _, _ = runs[engine]
    by_id = {s.id: s for s in tel.spans}
    parents: dict = {}
    for s in tel.spans:
        if s.name in PHASES[engine]:
            parents.setdefault(s.name, set()).add(by_id[s.parent].name)
    assert parents == {n: {p} for n, p in PHASES[engine].items()}


def test_dispatch_span_names_the_batching(runs):
    """Each group's ``dispatch`` span says which training program ran: the
    uniform pair stacks under ``vmap`` (the parity probe admits it), the
    single field unrolls."""
    tel, _, _ = runs["batched"]
    got = [s.attrs.get("batching") for s in sorted(tel.spans,
                                                   key=lambda s: s.t0)
           if s.name == "dispatch"]
    assert got == ["vmap", "unroll"]


def _expected_transfers(engine) -> dict:
    """The transfer counters of one ``_run`` from the shapes alone.

    Training groups hold ``group_size`` fields and the conventional stage
    runs once per group (one stacked call).  Its encode reads codes
    (int32), escape mask (bool) and target values (f64) at the initial
    lattice and at every phase of ``_interp_schedule``, then the f64
    reconstruction; every point of the lattice-padded field is a target
    exactly once.  Each group then reads its losses (f32 [epochs, F]) and
    one f32 residual per field, after uploading f64 padded fields to the
    conventional stage and f32 single-channel inputs and targets."""
    from repro.compressors import szlike
    cfg = neurlz.NeurLZConfig(engine=engine, epochs=EPOCHS)
    shape = FIELDS["f0"].shape
    level, phases = szlike._interp_schedule(shape,
                                            szlike.SZLikeConfig().max_level)
    s = 1 << level
    padded = int(np.prod([d if d == 1 else -(-(d - 1) // s) * s + 1
                          for d in shape]))
    size = int(np.prod(shape))
    names = list(FIELDS)
    syncs = d2h = h2d = 0
    for i in range(0, len(names), cfg.group_size):
        f = len(names[i:i + cfg.group_size])
        syncs += 3 * (1 + len(phases)) + 1 + 1 + f
        d2h += f * padded * (4 + 1 + 8 + 8) + EPOCHS * f * 4 + f * size * 4
        h2d += f * padded * 8 + f * 2 * size * 4
    return {"xfer.d2h_syncs": syncs, "xfer.d2h_bytes": d2h,
            "xfer.h2d_bytes": h2d}


@pytest.mark.parametrize("engine", sorted(PHASES))
def test_transfer_counters_match_the_shapes(runs, engine):
    tel, _, _ = runs[engine]
    got = tel.counters_prefixed("xfer.")
    assert got == _expected_transfers(engine)


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("predictor", ["interp", "lorenzo"])
def test_conv_entry_archives_identical_with_telemetry(predictor, batched):
    import pickle
    from repro.compressors import szlike
    cfg = szlike.SZLikeConfig(predictor=predictor)
    xs = [FIELDS["f0"], FIELDS["f1"]]

    def go(tel):
        if batched:
            return szlike.compress_batched(xs, 1e-3, config=cfg,
                                           telemetry=tel)
        return [szlike.compress(x, 1e-3, config=cfg, telemetry=tel)
                for x in xs]

    tel = obs.Telemetry()
    on, off = go(tel), go(obs.NULL)
    for (arc, rec), (arc0, rec0) in zip(on, off):
        assert pickle.dumps(arc) == pickle.dumps(arc0)
        assert rec.tobytes() == rec0.tobytes()
    calls = 1 if batched else len(xs)
    names = [s.name for s in tel.spans]
    assert names.count("entropy") == len(xs)
    assert names.count("interp") == (calls if predictor == "interp" else 0)
    _, phases = szlike._interp_schedule(xs[0].shape, cfg.max_level)
    per_call = 3 * (1 + len(phases)) + 1 if predictor == "interp" else 3
    assert tel.counters["xfer.d2h_syncs"] == calls * per_call


def test_entry_without_telemetry_kwarg_runs_through_conv_stage():
    """A registry entry that declares no ``telemetry`` keyword gets no
    handle; the stage still times and counts it."""
    import pickle
    from repro.compressors import registry, szlike
    from repro.core import conv_stage

    def plain(x, rel_eb=None, *, abs_eb=None):
        return szlike.compress(x, rel_eb, abs_eb=abs_eb)

    def plain_batched(xs, rel_eb=None, *, abs_eb=None):
        return szlike.compress_batched(xs, rel_eb, abs_eb=abs_eb)

    registry.register(registry.CompressorEntry(
        name="szlike-plain", kind="szlike", compress=plain,
        decompress=szlike.decompress, archive_nbytes=szlike.archive_nbytes,
        compress_batched=plain_batched,
        decompress_batched=szlike.decompress_batched,
        decode_key=szlike.decode_key))
    try:
        tel = obs.Telemetry()
        stage = conv_stage.ConvStage("szlike-plain", 1e-3, telemetry=tel)
        out = stage.run(FIELDS)
    finally:
        registry.unregister("szlike-plain")
    ref = conv_stage.ConvStage("szlike", 1e-3).run(FIELDS)
    for n in FIELDS:
        assert pickle.dumps(out[n][0]) == pickle.dumps(ref[n][0])
    assert stage.stats.calls == 1 and stage.stats.lowered_calls == 0
    assert {s.name for s in tel.spans} == {"conv"}
    assert tel.counters_prefixed("xfer.") == {}
    assert "conv.group_size" not in tel.gauges
