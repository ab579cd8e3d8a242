"""NeurLZ — the paper's contribution, end to end (§3.1, Fig. 3).

Compression:
  1. conventional error-bounded compression of every field (SZ3-like or
     ZFP-like), keeping the encoder-side reconstruction,
  2. per-field *online* training of a skipping-DNN enhancer on the residual
     ``X − X'`` (cross-field channels optional),
  3. error regulation: strict (store outlier coordinates) or relaxed
     (regulated 2× bound, nothing stored) or unregulated (ablation),
  4. package conventional payload + DNN weights + outliers into one archive.

Decompression mirrors it: conventional decode → enhancer inference →
``X̂ = X' + R̂`` → outlier patch.  All decoder inputs (normalization stats,
weights) come from the archive, and the conventional reconstruction is
bit-identical on both sides, so decode reproduces the encoder's enhanced
field exactly.

Three compression engines share this module's helpers:
  * ``engine="serial"``   — one field at a time, one dispatch per epoch per
    field; the reference implementation.
  * ``engine="batched"``  — the multi-field engine
    (:mod:`repro.core.batched_engine`): all fields of a snapshot train in a
    single dispatch per epoch, CPU-side conventional compression overlaps
    device-side training, and the stacked field axis can be sharded across
    devices.  Archives are bit-identical to the serial engine under the
    default ``field_batching="auto"`` strategy (stacked ``vmap`` for
    uniform groups, per-field unroll for ragged ones).
  * ``engine="streaming"`` — the bounded-memory pipeline
    (:mod:`repro.streaming`): fields are pulled lazily from a chunked
    source, conventional reconstructions are refcounted and evicted the
    moment their last cross-field consumer finishes, and entry packing +
    archival run on a writer thread under a hard ``max_resident_bytes``
    budget.  Entries are bit-identical to the serial engine's.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Mapping

import jax
import numpy as np

from .. import compressors
from .. import faults as faults_lib
from ..compressors import outliers as outlier_codec
from ..obs import telemetry as obs_lib
from . import archive as arc_io
from . import bounds as bounds_lib
from . import conv_stage as conv_stage_lib
from . import metrics, online_trainer, regulation, skipping_dnn


@dataclasses.dataclass(frozen=True)
class NeurLZConfig:
    compressor: str = "szlike"          # szlike | szlike-lorenzo | zfplike
    mode: str = "strict"                # strict | relaxed | unregulated
    epochs: int = 100
    batch: int = 10
    lr: float = 1e-2
    seed: int = 0
    slice_axis: int = 0
    skip: bool = True                   # skipping vs plain DNN (ablation)
    learn_residual: bool = True         # residual vs direct learning (ablation)
    cross_field: Mapping[str, tuple] = dataclasses.field(default_factory=dict)
    weight_dtype: str = "float32"       # archive precision for DNN weights
    widths: tuple = (4, 4, 6, 6, 8)
    engine: str = "serial"              # serial | batched | streaming
    conv_batch: bool = True             # snapshot-batched conventional stage
    field_batching: str = "auto"        # auto | unroll | vmap (stacked)
    lowering: str = "auto"              # eager | jit | pallas | auto — kernel
    #   lowering for the hot ops (repro.kernels.dispatch); every choice is
    #   byte-identical to eager or falls back, so archives never depend on it
    group_size: int = 2                 # fields per batched dispatch (0 = all)
    prefetch: bool = True               # overlap CPU conv stage with training
    field_shard: bool = True            # spread field groups over devices
    max_resident_bytes: int = 0         # streaming residency budget (0 = off)
    telemetry: object | None = None     # repro.obs.Telemetry handle (None =
    #   disabled: every instrumentation point is a shared no-op singleton)
    faults: object | None = None        # repro.faults.FaultConfig (None =
    #   defaults: no injection, no retries, conv-only degradation on)

    def net_config(self, c_in: int) -> skipping_dnn.SkippingDNNConfig:
        return skipping_dnn.SkippingDNNConfig(
            c_in=c_in, widths=self.widths,
            regulated=(self.mode != "unregulated"), skip=self.skip)

    def train_config(self) -> online_trainer.TrainConfig:
        return online_trainer.TrainConfig(
            epochs=self.epochs, batch=self.batch, lr=self.lr, seed=self.seed,
            slice_axis=self.slice_axis, lowering=self.lowering)


def _aux_names(cfg: NeurLZConfig, name: str, fields) -> list[str]:
    aux = list(cfg.cross_field.get(name, ()))
    missing = [a for a in aux if a not in fields]
    if missing:
        raise KeyError(f"cross-field aux {missing} not in input fields")
    return aux


def field_config(config: NeurLZConfig, mode: str | None) -> NeurLZConfig:
    """The effective config for one field under a per-field regulation mode
    (``None`` or the session mode -> the session config unchanged, which is
    what keeps legacy single-bound runs on the exact historical path)."""
    if mode is None or mode == config.mode:
        return config
    return dataclasses.replace(config, mode=mode)


_warned_shims: set[str] = set()


def _warn_legacy(fn: str, repl: str) -> None:
    """One ``DeprecationWarning`` per process per legacy dict-API shim."""
    if fn in _warned_shims:
        return
    _warned_shims.add(fn)
    warnings.warn(
        f"repro.core.{fn}() is a legacy dict-API shim; prefer {repl} "
        "(see the README migration table)", DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Helpers shared by both engines.  The batched engine builds entries through
# the very same functions, which is what keeps archives bit-compatible.
# ---------------------------------------------------------------------------

def build_dataset(x: np.ndarray, rec: np.ndarray, eb: float,
                  aux: list[np.ndarray], config: NeurLZConfig):
    """Per-field training tensors honoring the residual/direct ablation."""
    inputs, targets, stats = online_trainer.make_dataset(
        rec, x, eb, aux=aux, slice_axis=config.slice_axis)
    if not config.learn_residual:
        # Ablation: learn the normalized original directly (paper Fig. 4
        # "non-residual"), scaled by the decomp std so magnitudes match.
        mu, sd = stats[0]
        o = np.moveaxis(np.asarray(x, np.float64), config.slice_axis, 0)
        targets = (((o - mu) / sd).astype(np.float32))[..., None]
    return inputs, targets, stats


def pack_entry(config: NeurLZConfig, conv_arc: dict, params, stats,
               aux: list[str], eb: float, net_cfg, history,
               collect_stats: bool) -> dict:
    return {
        "conv": conv_arc,
        "weights": arc_io.pack_weights(params, config.weight_dtype),
        "stats": [list(s) for s in stats],
        "aux": aux,
        "mode": config.mode,
        "abs_eb": eb,
        "net": {"c_in": net_cfg.c_in, "widths": list(config.widths),
                "regulated": net_cfg.regulated, "skip": net_cfg.skip},
        "learn_residual": config.learn_residual,
        "loss_history": history if collect_stats else [],
    }


def pack_degraded_entry(config: NeurLZConfig, conv_arc: dict, eb: float,
                        reason: str) -> dict:
    """Conv-only entry for a field whose enhancer failed (non-finite loss,
    injected fault, OOM).  No weights/net — decode returns the conventional
    reconstruction, which already honors the exact ``abs_eb`` (the
    conventional stage guarantees ``|x - x'| <= eb``, tighter than both the
    strict 1x and relaxed 2x contracts).  ``reason`` is the normalized
    :func:`repro.faults.degrade_reason` string, so every engine emits a
    byte-identical entry for the same failure."""
    return {
        "conv": conv_arc,
        "stats": [],
        "aux": [],
        "mode": config.mode,
        "abs_eb": eb,
        "learn_residual": config.learn_residual,
        "loss_history": [],
        "degraded": reason,
    }


def history_is_finite(history) -> bool:
    """False when the training-loss trajectory went NaN/inf — the enhancer
    weights are poisoned from that epoch on, so the field degrades."""
    if not history:
        return True
    return bool(np.all(np.isfinite(np.asarray(history, dtype=np.float64))))


def enhance_and_mask(x: np.ndarray, rec: np.ndarray, resid_norm: np.ndarray,
                     eb: float, stats, config: NeurLZConfig):
    """Encoder-side enhancement; returns ``(field_rec, mask)`` where ``mask``
    is the strict-mode outlier mask (``None`` otherwise).  Split from
    :func:`finalize_entry` so the streaming pipeline can capture the mask on
    the compute thread and defer its *encoding* to the writer thread."""
    resid_norm = np.moveaxis(resid_norm, 0, config.slice_axis)
    if config.learn_residual:
        # Hot path: fused enhance + regulate + outlier capture through the
        # kernel-lowering dispatcher (byte-identical to the sequence below
        # by the dispatch parity contract).
        return regulation.enhance_lowered(
            rec, resid_norm, x, eb, out_dtype=x.dtype, mode=config.mode,
            lowering=config.lowering)
    field_rec = _apply_enhancement(rec, resid_norm, eb, x.dtype, stats, config)
    mask = None
    if config.mode == "strict":
        mask = regulation.outlier_mask(x, field_rec, eb)
        field_rec = regulation.apply_strict(field_rec, rec, mask)
    return field_rec, mask


def finalize_entry(entry: dict, x: np.ndarray, rec: np.ndarray,
                   resid_norm: np.ndarray, eb: float, stats,
                   config: NeurLZConfig, tel=obs_lib.NULL) -> np.ndarray:
    """Enhancement (``enhance`` span) + strict-mode outlier capture
    (``outliers`` span); mutates ``entry``."""
    with tel.span("enhance"):
        field_rec, mask = enhance_and_mask(x, rec, resid_norm, eb, stats,
                                           config)
    if mask is not None:
        with tel.span("outliers"):
            entry["outliers"] = outlier_codec.encode_outliers(mask)
    return field_rec


def assemble_archive(fields: Mapping[str, np.ndarray], out_fields: dict,
                     config: NeurLZConfig, timing: dict) -> dict:
    # Entries land in input-field order regardless of engine scheduling.
    arc = {
        "kind": "neurlz",
        "fields": {name: out_fields[name] for name in fields},
        "slice_axis": config.slice_axis,
        "compressor": config.compressor,
        "timing": timing,
    }
    arc["bitrate"] = {n: field_bitrate(arc, n, int(np.asarray(fields[n]).size))
                      for n in fields}
    return arc


def compress(fields: Mapping[str, np.ndarray], rel_eb: float | None = None, *,
             abs_eb: float | None = None, config: NeurLZConfig = NeurLZConfig(),
             collect_stats: bool = True, bounds=None) -> dict:
    """Compress a dict of fields of one snapshot into a NeurLZ archive dict.

    Legacy dict-API shim — :class:`repro.NeurLZ` / :class:`repro.Archive`
    are the first-class surface.  ``bounds`` optionally carries per-field
    :class:`repro.core.bounds.ErrorBound` specs (see
    :func:`repro.core.bounds.resolve_bounds` for the accepted forms).
    """
    _warn_legacy("compress", "repro.NeurLZ(...).compress(...)")
    return compress_impl(fields, rel_eb, abs_eb=abs_eb, config=config,
                         collect_stats=collect_stats, bounds=bounds)


def compress_impl(fields, rel_eb=None, *, abs_eb=None,
                  config: NeurLZConfig = NeurLZConfig(),
                  collect_stats: bool = True, bounds=None) -> dict:
    """Engine dispatch shared by the dict shim and the session API."""
    if config.engine == "batched":
        from . import batched_engine
        return batched_engine.compress(fields, rel_eb, abs_eb=abs_eb,
                                       config=config,
                                       collect_stats=collect_stats,
                                       bounds=bounds)
    if config.engine == "streaming":
        from ..streaming import pipeline
        return pipeline.compress_dict(fields, rel_eb, abs_eb=abs_eb,
                                      config=config,
                                      collect_stats=collect_stats,
                                      bounds=bounds)
    if config.engine != "serial":
        raise ValueError(f"unknown engine {config.engine!r} "
                         "(want 'serial', 'batched' or 'streaming')")
    return _compress_serial(fields, rel_eb, abs_eb=abs_eb, config=config,
                            collect_stats=collect_stats, bounds=bounds)


def field_vrange(x: np.ndarray) -> float:
    """Finite value range of a field (0.0 when nothing is finite) — the
    reference the learning-trace PSNR predictions are computed against."""
    v = np.asarray(x, dtype=np.float64)
    v = v[np.isfinite(v)]
    if v.size == 0:
        return 0.0
    return float(v.max() - v.min())


def entry_base_bytes(entry: dict) -> float:
    """Conv payload + enhancer weight bytes of a packed entry — the
    epoch-independent part of the learning-trace bitrate prediction."""
    return (compressors.archive_nbytes(entry["conv"])
            + (entry["weights"]["nbytes"] if "weights" in entry else 0))


def _sample_psnr_hook(tel, x, rec, inputs, eb, stats, config, net_cfg):
    """Per-epoch measured-PSNR hook for the serial trainer (telemetry
    ``sample_psnr`` mode): predicts the residual on a few sampled slices
    after every epoch and scores the pre-regulation enhancement against the
    original.  Returns ``(on_epoch, samples)`` — ``(None, None)`` when
    disabled (the fused engines have no per-epoch host hook)."""
    if not (tel.enabled and tel.config.sample_psnr):
        return None, None
    n = inputs.shape[0]
    k = max(1, min(int(tel.config.sample_slices), n))
    idx = np.linspace(0, n - 1, k).astype(int)
    x_s = np.moveaxis(np.asarray(x), config.slice_axis, 0)[idx]
    rec_s = np.moveaxis(np.asarray(rec), config.slice_axis, 0)[idx]
    inp_s = np.ascontiguousarray(inputs[idx])
    samples: list[float] = []

    def on_epoch(epoch, params, loss):
        resid = online_trainer.predict_residual(params, inp_s, net_cfg,
                                                lowering=config.lowering)
        enh = _apply_enhancement(rec_s, resid, eb, x_s.dtype, stats, config)
        samples.append(metrics.psnr(x_s, enh))

    return on_epoch, samples


def _compress_serial(fields, rel_eb, *, abs_eb, config, collect_stats,
                     bounds=None):
    tel = obs_lib.of(config)
    fc = faults_lib.of(config)
    t0 = time.time()
    with tel.span("compress", root=True, engine="serial",
                  fields=len(fields)):
        # Per-field error-bound specs (None -> legacy single-scalar path).
        resolved = None
        if bounds is not None:
            resolved = bounds_lib.resolve_bounds(list(fields), bounds,
                                                 rel_eb, abs_eb,
                                                 default_mode=config.mode)
        # Shared conventional stage: the whole snapshot is one plan, so
        # fields sharing a (shape, dtype, bound spec) compress through the
        # fused entry.
        stage = conv_stage_lib.ConvStage(config.compressor, rel_eb, abs_eb,
                                         batch=config.conv_batch,
                                         bounds=resolved, telemetry=tel,
                                         lowering=config.lowering)
        conv = stage.run(fields)
        conv_arcs = {n: arc for n, (arc, _) in conv.items()}
        recs = {n: rec for n, (_, rec) in conv.items()}
        ebs = {n: arc["abs_eb"] for n, arc in conv_arcs.items()}

        # A reconstruction stays resident only until its last consumer (its
        # own finalize + every field listing it as cross-field aux) is done
        # — the streaming pipeline's refcount idea in miniature.
        rec_refs = {n: 1 for n in fields}
        for n in fields:
            for a in _aux_names(config, n, fields):
                rec_refs[a] += 1

        out_fields = {}
        degraded: list[str] = []
        train_time = 0.0
        for name, x in fields.items():
            x = np.asarray(x)
            eb = ebs[name]
            fcfg = field_config(config,
                                resolved[name].mode if resolved else None)
            aux_names = _aux_names(fcfg, name, fields)
            aux = [recs[a] for a in aux_names]
            net_cfg = fcfg.net_config(1 + len(aux))
            tcfg = fcfg.train_config()

            entry, sampled, reason = None, None, None
            with tel.span("train", field=name):
                try:
                    fc.check(f"train.{name}")
                    inputs, targets, stats = build_dataset(x, recs[name], eb,
                                                           aux, fcfg)

                    key = jax.random.PRNGKey(tcfg.seed)
                    params = skipping_dnn.init_params(key, net_cfg)
                    on_epoch, sampled = _sample_psnr_hook(
                        tel, x, recs[name], inputs, eb, stats, fcfg, net_cfg)
                    tt = time.time()
                    params, _, history = online_trainer.train(
                        params, inputs, targets, tcfg, net_cfg,
                        on_epoch=on_epoch)
                    train_time += time.time() - tt

                    if fc.degrade and not history_is_finite(history):
                        reason = faults_lib.degrade_reason()
                    else:
                        resid_norm = online_trainer.predict_residual(
                            params, inputs, net_cfg,
                            lowering=fcfg.lowering)
                        entry = pack_entry(fcfg, conv_arcs[name], params,
                                           stats, aux_names, eb, net_cfg,
                                           history, collect_stats)
                        finalize_entry(entry, x, recs[name], resid_norm, eb,
                                       stats, fcfg)
                except Exception as exc:
                    if not (fc.degrade and faults_lib.is_degradable(exc)):
                        raise
                    reason = faults_lib.degrade_reason(exc)
            if reason is not None:
                entry = pack_degraded_entry(fcfg, conv_arcs[name], eb, reason)
                degraded.append(name)
                tel.counter("faults.degraded").add()
            elif tel.enabled and tel.config.learning_traces:
                obs_lib.learning_trace(
                    tel, name, history, eb=eb, vrange=field_vrange(x),
                    base_bytes=entry_base_bytes(entry), n_points=int(x.size),
                    mode=fcfg.mode, sample_psnr=sampled)
            out_fields[name] = entry
            for m in (name, *aux_names):
                rec_refs[m] -= 1
                if rec_refs[m] <= 0:
                    recs.pop(m, None)

        timing = obs_lib.build_timing(
            tel, total_s=time.time() - t0, conv_s=stage.stats.conv_s,
            train_s=train_time, conv_stage=stage.stats.as_dict(),
            degraded_fields=degraded)
        with tel.span("assemble"):
            return assemble_archive(fields, out_fields, config, timing)


def _apply_enhancement(rec, resid_norm, eb, out_dtype, stats, config) -> np.ndarray:
    if config.learn_residual:
        return regulation.enhance(rec, resid_norm, eb, out_dtype)
    # Direct-learning ablation: the net predicts the normalized value itself.
    mu, sd = stats[0]
    return (resid_norm.astype(np.float64) * sd + mu).astype(out_dtype)


def decode_entry_net(entry: dict):
    """Rebuild (net_cfg, params) for one archived field entry."""
    net = entry["net"]
    net_cfg = skipping_dnn.SkippingDNNConfig(
        c_in=net["c_in"], widths=tuple(net["widths"]),
        regulated=net["regulated"], skip=net["skip"])
    params = skipping_dnn.init_params(jax.random.PRNGKey(0), net_cfg)
    params = arc_io.unpack_weights(entry["weights"], params)
    return net_cfg, params


def apply_decoded_entry(entry: dict, rec: np.ndarray, resid_norm: np.ndarray,
                        slice_axis: int) -> np.ndarray:
    """Decode-side enhancement + outlier patch from archived metadata."""
    eb = entry["abs_eb"]
    resid_norm = np.moveaxis(resid_norm, 0, slice_axis)
    stats = [tuple(s) for s in entry["stats"]]
    dtype = np.dtype(entry["conv"]["dtype"])
    cfg = NeurLZConfig(mode=entry["mode"],
                       learn_residual=entry["learn_residual"])
    out = _apply_enhancement(rec, resid_norm, eb, dtype, stats, cfg)
    if entry["mode"] == "strict" and "outliers" in entry:
        mask = outlier_codec.decode_outliers(entry["outliers"])
        out = regulation.apply_strict(out, rec, mask)
    return out


def decode_field_entry(e: dict, rec: np.ndarray, aux: list,
                       slice_axis: int) -> np.ndarray:
    """Full single-field decode from its archive entry + conventional
    reconstructions (its own and its aux fields'): enhancer inference +
    enhancement + outlier patching.  The one decode body shared by the
    serial path, streaming ``iter_decompress`` and ``Archive.decode``."""
    if e.get("degraded"):
        # Conv-only entry (enhancer failure at compress time): the
        # conventional reconstruction IS the decode, bound already honored.
        return np.asarray(rec)
    net_cfg, params = decode_entry_net(e)
    stats = [tuple(s) for s in e["stats"]]
    inputs, _, _ = online_trainer.make_dataset(
        rec, None, e["abs_eb"], aux=aux, slice_axis=slice_axis, stats=stats)
    resid_norm = online_trainer.predict_residual(params, inputs, net_cfg)
    return apply_decoded_entry(e, rec, resid_norm, slice_axis)


def decompress(arc, *, engine: str = "serial") -> dict[str, np.ndarray]:
    """Full decode: conventional + enhancer inference + outlier patching.

    Legacy dict-API shim over :func:`decompress_impl` (prefer
    ``Archive.decode`` / ``Archive.decode_all``).  ``engine="batched"``
    runs every field's enhancer inference in a single dispatch
    (bit-identical output — the batched path inlines the exact serial
    inference graph per field).  Accepts archive dicts and
    :class:`repro.core.archive_api.Archive` handles alike.
    """
    _warn_legacy("decompress", "Archive.decode_all(...) / Archive.decode(...)")
    return decompress_impl(arc, engine=engine)


def decompress_impl(arc, *, engine: str = "serial") -> dict[str, np.ndarray]:
    if engine == "batched":
        from . import batched_engine
        return batched_engine.decompress(arc)
    slice_axis = arc["slice_axis"]
    recs = {name: compressors.decompress(e["conv"])
            for name, e in arc["fields"].items()}
    out = {}
    for name, e in arc["fields"].items():
        aux = [recs[a] for a in e["aux"]]
        out[name] = decode_field_entry(e, recs[name], aux, slice_axis)
    return out


def field_bitrate(arc: dict, name: str, num_points: int) -> dict:
    """Paper bit-rate accounting: size(Z) + supplementary, bits/value."""
    e = arc["fields"][name]
    conv_b = compressors.archive_nbytes(e["conv"])
    weight_b = e["weights"]["nbytes"] if "weights" in e else 0.0
    out_b = 0.0
    out_bits_paper = 0.0
    if "outliers" in e:
        out_b = e["outliers"]["nbytes"]
        out_bits_paper = e["outliers"]["packed_bits"]
    total = conv_b + weight_b + out_b
    return {
        "conv_bytes": conv_b,
        "weight_bytes": weight_b,
        "outlier_bytes": out_b,
        "outlier_bits_paper_formula": out_bits_paper,
        "total_bytes": total,
        "bitrate": metrics.bitrate(total, num_points),
        "conv_bitrate": metrics.bitrate(conv_b, num_points),
    }


def save(path: str, arc: dict) -> int:
    """Write a whole-dict archive file.  Legacy dict-API shim: an
    :class:`Archive` handle is materialized first, preserving the historical
    ``save(load(streaming_path))`` round-trip, which converted a streaming
    container into the whole-dict format.  (``Archive.save`` instead keeps
    the native container and copies bytes.)"""
    _warn_legacy("save", "Archive.save(path)")
    from . import archive_api
    if isinstance(arc, archive_api.Archive):
        arc = arc.to_dict()
    return arc_io.save(path, arc)


def assemble_streaming_archive(reader: arc_io.ArchiveReader) -> dict:
    """Reassemble a streaming container into the whole-dict archive format.

    Entries land in the snapshot's input-field order (recorded in the index
    footer), so the result is byte-compatible with what the in-memory
    engines produce.
    """
    meta = reader.meta
    fields = {name: reader.read_entry(name) for name in meta["field_order"]}
    arc = {
        "kind": "neurlz",
        "fields": fields,
        "slice_axis": meta["slice_axis"],
        "compressor": meta["compressor"],
        "timing": meta.get("timing", {}),
    }
    arc["bitrate"] = {
        n: field_bitrate(arc, n, int(np.prod(meta["shapes"][n])))
        for n in fields}
    return arc


def load(path: str):
    """Open an archive file (either container format).

    Legacy dict-API shim.  A whole-dict file loads into the plain archive
    dict exactly as before.  A streaming (``NLZSTRM1``) container now comes
    back as a **lazy** :class:`repro.core.archive_api.Archive` handle —
    dict-compatible for reads (``arc["fields"]`` etc. materialize on first
    access) but O(1) in resident bytes at open time, fixing the regression
    where opening an out-of-core archive reassembled every field in
    memory.  Two contract deltas for that case: the handle is a *read-only*
    mapping (mutate ``arc.to_dict()`` instead), and it holds the container
    file open — call ``arc.close()`` (or use it as a context manager) when
    done; dropping the last reference also closes it.
    """
    _warn_legacy("load", "repro.Archive.open(path)")
    if arc_io.is_streaming_archive(path):
        from . import archive_api
        return archive_api.Archive.open(path)
    return arc_io.load(path)
