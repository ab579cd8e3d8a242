"""Kernel-lowering dispatch: resolution, probes, fallbacks, and the archive
bit-stability contract (`lowering="jit"`/"auto" byte-identical to "eager"
for every engine and every compressor)."""
import dataclasses
import pickle
import warnings

import numpy as np
import pytest

from repro.core import batched_engine, conv_stage, neurlz, regulation
from repro.kernels import dispatch

warnings.simplefilter("ignore", DeprecationWarning)


# ---------------------------------------------------------------------------
# Dispatch mechanics
# ---------------------------------------------------------------------------

def test_resolve_rejects_unknown_lowering():
    with pytest.raises(ValueError, match="unknown lowering"):
        dispatch.resolve("dnn_forward", "fastest")


def test_resolve_rejects_unknown_op():
    with pytest.raises(KeyError, match="no registered eager reference"):
        dispatch.resolve("no_such_op", "eager")


def test_register_rejects_auto_as_variant():
    with pytest.raises(ValueError):
        dispatch.register("x", "auto", lambda: None)


def test_probe_failure_falls_back_and_is_recorded():
    calls = []
    dispatch.register("_test_op", "eager", lambda: "eager")
    dispatch.register("_test_op", "jit", lambda: "jit",
                      probe=lambda: calls.append(1) or False)
    try:
        fn, chosen = dispatch.resolve("_test_op", "jit")
        assert chosen == "eager" and fn() == "eager"
        assert ("_test_op", "jit", dispatch.backend(),
                dispatch.MISMATCH) in dispatch.fallbacks()
        # verdict is cached: a second resolve must not re-probe
        dispatch.resolve("_test_op", "jit")
        assert len(calls) == 1
    finally:
        dispatch._ops.pop("_test_op", None)
        dispatch.clear_cache()


def test_probe_exception_counts_as_failure():
    def boom():
        raise RuntimeError("cannot even run\nsecond line")

    dispatch.register("_test_op2", "eager", lambda: "eager")
    dispatch.register("_test_op2", "pallas", lambda: "pallas", probe=boom)
    try:
        fn, chosen = dispatch.resolve("_test_op2", "pallas")
        assert chosen == "eager"
        # Reported as raised (type + first line), never as a mismatch.
        reason = f"{dispatch.RAISED} RuntimeError: cannot even run"
        assert ("_test_op2", "pallas", dispatch.backend(),
                reason) in dispatch.fallbacks()
        assert not any(f[0] == "_test_op2" and f[3] == dispatch.MISMATCH
                       for f in dispatch.fallbacks())
        assert dispatch.parity_report()["_test_op2"]["pallas"] == \
            f"fallback ({reason})"
    finally:
        dispatch._ops.pop("_test_op2", None)
        dispatch.clear_cache()


def test_auto_prefers_probe_passing_variant():
    dispatch.register("_test_op3", "eager", lambda: "eager")
    dispatch.register("_test_op3", "jit", lambda: "jit", probe=lambda: True)
    dispatch.register("_test_op3", "pallas", lambda: "pallas",
                      backends=("tpu",))
    try:
        _, chosen = dispatch.resolve("_test_op3", "auto")
        # pallas is TPU-gated -> jit wins on this box
        expect = "pallas" if dispatch.backend() == "tpu" else "jit"
        assert chosen == expect
    finally:
        dispatch._ops.pop("_test_op3", None)
        dispatch.clear_cache()


def test_backend_is_cached_and_forcible():
    b0 = dispatch.backend()
    with dispatch.force_backend("tpu"):
        assert dispatch.backend() == "tpu"
    assert dispatch.backend() == b0


def test_force_backend_drops_forced_verdicts():
    dispatch.register("_test_op4", "eager", lambda: "eager")
    dispatch.register("_test_op4", "jit", lambda: "jit", probe=lambda: True)
    try:
        with dispatch.force_backend("tpu"):
            dispatch.resolve("_test_op4", "jit")
            assert any(k[2] == "tpu" for k in dispatch._verdicts)
        assert not any(k[2] == "tpu" for k in dispatch._verdicts)
    finally:
        dispatch._ops.pop("_test_op4", None)
        dispatch.clear_cache()


def test_tpu_gated_variants_fall_back_on_cpu():
    if dispatch.backend() == "tpu":
        pytest.skip("CPU-only check")
    for op in ("dnn_forward", "lorenzo", "fused_enhance"):
        _, chosen = dispatch.resolve(op, "pallas")
        assert chosen == "eager", op
    assert any(f[0] == "lorenzo" and f[1] == "pallas"
               and "unsupported" in f[3] for f in dispatch.fallbacks())
    # dnn_forward has no Pallas variant (it runs under value_and_grad).
    assert any(f[0] == "dnn_forward" and f[1] == "pallas"
               and f[3] == "unregistered" for f in dispatch.fallbacks())


def test_parity_report_covers_all_ops():
    dispatch._register_all()
    report = dispatch.parity_report()
    assert {"dnn_forward", "lorenzo", "fused_enhance"} <= set(report)
    for rows in report.values():
        assert set(rows) == {"jit", "pallas"}


# ---------------------------------------------------------------------------
# Per-op parity on this backend
# ---------------------------------------------------------------------------

def test_lorenzo_jit_passes_parity_probe():
    from repro.compressors import szlike
    assert szlike._lorenzo_jit_probe()
    _, chosen = dispatch.resolve("lorenzo", "jit")
    assert chosen == "jit"


def test_fused_enhance_jit_passes_parity_probe():
    # x64 is enabled package-wide, so the jnp float64 mirror (with its FMA
    # barrier) is byte-identical to the numpy eager reference.
    assert regulation._probe_variant(regulation._fused_enhance_jit)
    _, chosen = dispatch.resolve("fused_enhance", "jit")
    assert chosen == "jit"


def test_fused_enhance_lowered_bytes_match_eager():
    d, r, o, eb = regulation._enhance_canaries()
    for mode in ("strict", "relaxed", "unregulated"):
        for low in ("eager", "jit", "auto"):
            rec, mask = regulation.enhance_lowered(
                d, r, o, eb, out_dtype=np.float32, mode=mode, lowering=low)
            rec0, mask0 = regulation.fused_enhance(
                d, r, o, eb, out_dtype=np.float32, mode=mode)
            assert rec.tobytes() == rec0.tobytes(), (mode, low)
            assert (mask is None) == (mask0 is None)
            if mask is not None:
                assert mask.tobytes() == mask0.tobytes()


# ---------------------------------------------------------------------------
# ConvStage lowering passthrough
# ---------------------------------------------------------------------------

def test_accepts_lowering_signature_inspection():
    assert conv_stage._accepts_lowering(lambda x, *, lowering="auto": x)
    assert conv_stage._accepts_lowering(lambda x, **kw: x)
    assert not conv_stage._accepts_lowering(lambda x, rel_eb: x)


@pytest.mark.parametrize("compressor", ["szlike", "szlike-lorenzo",
                                        "zfplike"])
def test_conv_stage_threads_lowering(compressor):
    rng = np.random.default_rng(0)
    fields = {f"f{i}": np.cumsum(
        rng.standard_normal((6, 8, 8)).astype(np.float32), axis=0)
        for i in range(2)}
    base = conv_stage.ConvStage(compressor, 1e-3, lowering="eager").run(fields)
    for low in ("jit", "auto"):
        stage = conv_stage.ConvStage(compressor, 1e-3, lowering=low)
        out = stage.run(fields)
        for n in fields:
            assert pickle.dumps(out[n][0]) == pickle.dumps(base[n][0]), \
                (compressor, low, n)
            assert out[n][1].tobytes() == base[n][1].tobytes()
        # szlike entries declare the kwarg; third-party-style zfplike doesn't
        if compressor == "zfplike":
            assert stage.stats.lowered_calls == 0
        else:
            assert stage.stats.lowered_calls == stage.stats.calls
        assert stage.stats.lowering == low


# ---------------------------------------------------------------------------
# field_batching="auto" resolution
# ---------------------------------------------------------------------------

def test_resolve_batching():
    assert batched_engine.resolve_batching("unroll", [4, 4]) == "unroll"
    assert batched_engine.resolve_batching("vmap", [4, 5]) == "vmap"
    assert batched_engine.resolve_batching("auto", [4, 4]) == "vmap"
    assert batched_engine.resolve_batching("auto", [4, 5]) == "unroll"
    assert batched_engine.resolve_batching("auto", [4]) == "unroll"


def test_unknown_field_batching_raises():
    rng = np.random.default_rng(1)
    fields = {"a": np.cumsum(
        rng.standard_normal((6, 8, 8)).astype(np.float32), axis=0)}
    cfg = neurlz.NeurLZConfig(engine="batched", epochs=1,
                              field_batching="wat")
    with pytest.raises(ValueError, match="field_batching"):
        neurlz.compress_impl(fields, 1e-3, config=cfg)


# ---------------------------------------------------------------------------
# The contract: archives are byte-identical across lowerings for every
# engine and every compressor.
# ---------------------------------------------------------------------------

def _fields(uniform=True):
    rng = np.random.default_rng(11)
    shapes = [(10, 10, 8)] * 2 if uniform else [(10, 10, 8), (13, 10, 8)]
    return {f"f{i}": np.cumsum(
        rng.standard_normal(s).astype(np.float32), axis=0)
        for i, s in enumerate(shapes)}


def _entries(fields, config, tmp_path=None):
    if config.engine == "streaming":
        from repro.streaming import pipeline
        arc = pipeline.compress_dict(fields, 1e-3, config=config,
                                     collect_stats=True)
    else:
        arc = neurlz.compress_impl(fields, 1e-3, config=config)
    return pickle.dumps(arc["fields"])


@pytest.mark.parametrize("engine", ["serial", "batched", "streaming"])
@pytest.mark.parametrize("compressor", ["szlike", "szlike-lorenzo",
                                        "zfplike"])
def test_archive_bytes_invariant_across_lowerings(engine, compressor):
    fields = _fields()
    base_cfg = neurlz.NeurLZConfig(engine=engine, compressor=compressor,
                                   epochs=2, group_size=0)
    want = _entries(fields, dataclasses.replace(base_cfg, lowering="eager"))
    for low in ("jit", "auto"):
        got = _entries(fields, dataclasses.replace(base_cfg, lowering=low))
        assert got == want, (engine, compressor, low)


def test_archive_bytes_invariant_ragged_groups():
    # Ragged slice counts force auto -> unroll; still byte-identical.
    fields = _fields(uniform=False)
    base_cfg = neurlz.NeurLZConfig(engine="batched", epochs=2, group_size=0)
    want = _entries(fields, dataclasses.replace(base_cfg, lowering="eager",
                                                field_batching="unroll"))
    got = _entries(fields, base_cfg)   # lowering=auto, field_batching=auto
    assert got == want


def test_auto_batching_bytes_match_serial():
    # Uniform groups under the auto default: whatever strategy the parity
    # probe admits, the archive must round-trip bit-exact against serial.
    fields = _fields(uniform=True)
    serial = _entries(fields, neurlz.NeurLZConfig(epochs=2))
    auto = _entries(fields, neurlz.NeurLZConfig(
        engine="batched", epochs=2, group_size=0))
    assert auto == serial


def test_explicit_vmap_bytes_match_serial_when_probe_passes():
    # Explicit vmap is best-effort max batching; the probe is the oracle
    # for whether this box's XLA lowers the stacked gradient identically
    # at this signature.
    fields = _fields(uniform=True)
    cfg = neurlz.NeurLZConfig(engine="batched", epochs=2, group_size=0,
                              field_batching="vmap")
    shape = next(iter(fields.values())).shape
    parity = batched_engine.vmap_bit_parity(
        cfg.net_config(1), shape[1:], min(cfg.batch, shape[0]),
        cfg.train_config())
    if not parity:
        pytest.skip("stacked gradient not bit-identical at this signature")
    serial = _entries(fields, neurlz.NeurLZConfig(epochs=2))
    assert _entries(fields, cfg) == serial


def test_vmap_parity_probe_is_cached():
    cfg = neurlz.NeurLZConfig()
    net = cfg.net_config(1)
    tcfg = cfg.train_config()
    v1 = batched_engine.vmap_bit_parity(net, (10, 8), 10, tcfg)
    key = ((10, 8), 1, 10, net.regulated, net.skip, tcfg.loss, tcfg.lowering)
    assert batched_engine._vmap_parity[key] == v1
    assert batched_engine.vmap_bit_parity(net, (10, 8), 10, tcfg) == v1


def test_decode_matches_across_lowerings():
    fields = _fields()
    cfg = neurlz.NeurLZConfig(epochs=2)
    arc = neurlz.compress_impl(fields, 1e-3, config=cfg)
    eager = neurlz.decompress_impl(arc)
    for engine in ("serial", "batched"):
        out = neurlz.decompress_impl(arc, engine=engine)
        for n in fields:
            assert out[n].tobytes() == eager[n].tobytes(), (engine, n)
