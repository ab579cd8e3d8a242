"""Compressor registry + shared conventional stage.

Covers the registry contract (hard errors for unknown names/kinds, the old
``archive_nbytes`` fall-through regression, third-party registration) and
the conv-stage byte-identity matrix: batched group compression must produce
payloads byte-identical to the per-field path, for every built-in
compressor, across all three engines.
"""
import numpy as np
import pytest

from repro import compressors as C
from repro import core
from repro.compressors import registry
from repro.core import archive as arc_io
from repro.core import conv_stage


def smooth_field(shape, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    for ax in range(len(shape)):
        x = np.cumsum(x, axis=ax)
    x /= max(np.abs(x).max(), 1e-9)
    return x.astype(dtype)


COMPRESSORS = ["szlike", "szlike-lorenzo", "zfplike"]


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------

def test_unknown_compressor_raises():
    with pytest.raises(ValueError, match="unknown compressor"):
        C.compress(smooth_field((8, 8)), 1e-3, compressor="nope")
    with pytest.raises(ValueError, match="unknown compressor"):
        conv_stage.ConvStage("nope", 1e-3)


def test_unknown_archive_kind_raises():
    """Regression: ``archive_nbytes`` used to silently fall through to the
    zfplike accounting for unknown kinds; both decode-side dispatches must
    hard-error now."""
    with pytest.raises(ValueError, match="unknown archive kind"):
        C.archive_nbytes({"kind": "mystery", "nbytes": 7})
    with pytest.raises(ValueError, match="unknown archive kind"):
        C.decompress({"kind": "mystery"})
    with pytest.raises(ValueError, match="unknown archive kind"):
        C.archive_nbytes({})    # no kind tag at all


def test_builtins_registered_with_capabilities():
    assert registry.names() == sorted(COMPRESSORS)
    for name in COMPRESSORS:
        entry = registry.get(name)
        assert entry.batchable
        assert entry.batch_supports(np.float32)
        assert entry.batch_supports(np.float64)
        assert not entry.batch_supports(np.int32)


def test_register_custom_compressor():
    """A third-party compressor is a registration, not a core edit."""

    def raw_compress(x, rel_eb=None, *, abs_eb=None, **kw):
        x = np.asarray(x)
        arc = {"kind": "rawcopy", "dtype": str(x.dtype),
               "shape": list(x.shape), "payload": x.tobytes(),
               "abs_eb": float(abs_eb if abs_eb is not None else 0.0)}
        return arc, x.copy()

    def raw_decompress(arc):
        return np.frombuffer(arc["payload"],
                             dtype=arc["dtype"]).reshape(arc["shape"]).copy()

    entry = registry.CompressorEntry(
        name="rawcopy", kind="rawcopy", compress=raw_compress,
        decompress=raw_decompress,
        archive_nbytes=lambda arc: len(arc["payload"]))
    registry.register(entry)
    try:
        x = smooth_field((6, 7))
        arc, rec = C.compress(x, abs_eb=0.0, compressor="rawcopy")
        assert np.array_equal(C.decompress(arc), x)
        assert C.archive_nbytes(arc) == x.nbytes
        # Duplicate names are rejected unless overwritten explicitly.
        with pytest.raises(ValueError, match="already registered"):
            registry.register(entry)
        # Not batchable -> the conv stage falls back per-field.
        stage = conv_stage.ConvStage("rawcopy", abs_eb=0.0)
        fields = {f"f{i}": smooth_field((6, 7), seed=i) for i in range(3)}
        out = stage.run(fields)
        assert set(out) == set(fields)
        assert stage.stats.calls == 3
        assert stage.stats.batched_fields == 0
        assert stage.stats.fallback_fields == 3
    finally:
        registry.unregister("rawcopy")
    with pytest.raises(ValueError, match="unknown archive kind"):
        C.archive_nbytes(arc)


def test_kind_conflict_rejected():
    bad = registry.CompressorEntry(
        name="szlike-impostor", kind="szlike",
        compress=lambda *a, **k: None, decompress=lambda a: None,
        archive_nbytes=lambda a: 0)
    with pytest.raises(ValueError, match="kind"):
        registry.register(bad)


# ---------------------------------------------------------------------------
# Conv-stage batched execution: byte-identity + dispatch accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp", COMPRESSORS)
def test_stage_batched_byte_identical_to_per_field(comp):
    fields = {
        "a0": smooth_field((10, 12, 8), seed=0),
        "a1": smooth_field((10, 12, 8), seed=1),
        "a2": smooth_field((10, 12, 8), seed=2),
        "b0": smooth_field((10, 12, 8), seed=3, dtype=np.float64),
        "c0": smooth_field((9, 7), seed=4),
    }
    fields["a1"][2, 3, 4] = np.nan     # literal-escape path rides along
    batched = conv_stage.ConvStage(comp, 1e-3).run(fields)
    per_field = conv_stage.ConvStage(comp, 1e-3, batch=False).run(fields)
    for name in fields:
        arc_b, rec_b = batched[name]
        arc_p, rec_p = per_field[name]
        assert arc_io.dumps(arc_b) == arc_io.dumps(arc_p), name
        assert np.array_equal(rec_b, rec_p, equal_nan=True), name
        assert C.archive_nbytes(arc_b) == C.archive_nbytes(arc_p)


def test_stage_stats_group_accounting():
    fields = {f"f{i}": smooth_field((8, 10, 8), seed=i) for i in range(4)}
    fields["g64"] = smooth_field((8, 10, 8), seed=9, dtype=np.float64)
    fields["h2d"] = smooth_field((9, 7), seed=10)
    stage = conv_stage.ConvStage("szlike", 1e-3)
    stage.run(fields)
    st = stage.stats
    assert st.fields == 6
    assert st.groups == 3              # (f32 3-D) + (f64 3-D) + (f32 2-D)
    assert st.batched_fields == 4      # the four same-signature fields
    assert st.fallback_fields == 2     # singleton groups run per-field
    assert st.calls == 3               # 1 fused + 2 singles < 6 fields


@pytest.mark.parametrize("cap", [1, 2, 3])
def test_stage_stacks_within_the_device_memory_cap(monkeypatch, cap):
    """Where the device reports a memory limit, a same-signature group is
    stacked only in runs that fit it (a 512³ group exhausts a 16 GiB chip);
    payloads and reconstructions stay byte-identical to the uncapped run."""
    fields = {f"f{i}": smooth_field((8, 10, 8), seed=i) for i in range(4)}
    free = conv_stage.ConvStage("szlike", 1e-3).run(fields)
    arcs = {n: a for n, (a, _) in free.items()}
    monkeypatch.setattr(registry, "stack_limit", lambda points: cap)
    stage = conv_stage.ConvStage("szlike", 1e-3)
    capped = stage.run(fields)
    for name in fields:
        assert arc_io.dumps(capped[name][0]) == arc_io.dumps(arcs[name])
        assert np.array_equal(capped[name][1], free[name][1])
    runs = [min(cap, 4 - i) for i in range(0, 4, cap)]
    assert stage.stats.calls == len(runs)
    assert stage.stats.fallback_fields == runs.count(1)
    widths = []

    class Stats:
        def note(self, w):
            widths.append(w)

    recs = registry.decompress_many(arcs, stats=Stats())
    assert widths == runs
    for name in fields:
        assert np.array_equal(recs[name], free[name][1])


def test_stack_limit_is_off_without_a_reported_limit():
    # The CPU backend reports no memory limit: nothing is capped there.
    assert registry.stack_limit(512 ** 3) is None
    assert registry.stack_chunks(list("abc"), 512 ** 3) == [list("abc")]


# ---------------------------------------------------------------------------
# Engines x compressors byte-identity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp", COMPRESSORS)
def test_engine_matrix_conv_payloads_identical(comp):
    """Serial/batched/streaming engines must emit byte-identical conventional
    payloads (and sizes) for the same snapshot — whichever conv-stage path
    (fused group or per-field fallback) compressed each field."""
    fields = {
        "a0": smooth_field((6, 10, 8), seed=0),
        "a1": smooth_field((6, 10, 8), seed=1),
        "a2": smooth_field((6, 10, 8), seed=2),
        "b0": smooth_field((6, 10, 8), seed=3, dtype=np.float64),
    }
    reference = None
    for engine in ("serial", "batched", "streaming"):
        cfg = core.NeurLZConfig(compressor=comp, epochs=1, mode="strict",
                                engine=engine,
                                cross_field={"a1": ("a0",)})
        arc = core.compress(fields, rel_eb=1e-3, config=cfg)
        convs = {n: arc_io.dumps(arc["fields"][n]["conv"]) for n in fields}
        sizes = {n: C.archive_nbytes(arc["fields"][n]["conv"])
                 for n in fields}
        stats = arc["timing"]["conv_stage"]
        assert stats["fields"] == len(fields)
        assert stats["calls"] < stats["fields"], engine
        if reference is None:
            reference = (convs, sizes)
        else:
            assert convs == reference[0], (comp, engine)
            assert sizes == reference[1], (comp, engine)
    # The per-field stage (conv_batch=False) agrees too.
    cfg0 = core.NeurLZConfig(compressor=comp, epochs=1, mode="strict",
                             engine="serial", conv_batch=False,
                             cross_field={"a1": ("a0",)})
    arc0 = core.compress(fields, rel_eb=1e-3, config=cfg0)
    assert {n: arc_io.dumps(arc0["fields"][n]["conv"])
            for n in fields} == reference[0]


# ---------------------------------------------------------------------------
# Property: mixed shapes/dtypes never break batched == per-field
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # hypothesis is an optional [dev] extra
    HAVE_HYPOTHESIS = False


def _mk_fields(seed: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    shapes = [(6, 8, 8), (5, 7), (6, 8, 8), (4, 9, 5)]
    out = {}
    for i in range(int(rng.integers(2, 5))):
        shape = shapes[int(rng.integers(0, len(shapes)))]
        dtype = np.float64 if (seed + i) % 3 == 0 else np.float32
        x = rng.standard_normal(shape)
        for ax in range(len(shape)):
            x = np.cumsum(x, axis=ax)
        out[f"f{i}"] = x.astype(dtype)
    return out


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000), eb=st.sampled_from([1e-2, 1e-3]),
           comp=st.sampled_from(COMPRESSORS))
    def test_property_stage_byte_identity(seed, eb, comp):
        fields = _mk_fields(seed)
        batched = conv_stage.ConvStage(comp, eb).run(fields)
        per_field = conv_stage.ConvStage(comp, eb, batch=False).run(fields)
        for name in fields:
            assert arc_io.dumps(batched[name][0]) \
                == arc_io.dumps(per_field[name][0])
            assert np.array_equal(batched[name][1], per_field[name][1])
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_stage_byte_identity():
        pass
