"""Prediction-based error-bounded lossy compressor (SZ3-style), in JAX.

Two predictors, selectable per SZ3's design space:

* ``interp`` — multilevel spline interpolation (SZ3 default, Zhao et al.
  ICDE'21): reconstruct a coarse lattice first, then refine level by level,
  axis by axis, predicting every midpoint by cubic interpolation of already-
  reconstructed neighbors.  Each phase is a fully vectorized stencil — this is
  the TPU-native reformulation (DESIGN.md §3): within a level there are no
  sequential dependencies, so the whole phase is one fused jnp expression.

* ``lorenzo`` — cuSZ-style *dual-quantization* Lorenzo: pre-quantize the field
  onto the ``2*eb`` lattice, then take the 3-D first-order Lorenzo delta of
  the integer grid.  Both directions are pure stencils/prefix-sums (the
  sequential SZ1.4 recurrence is gone); the forward pass is the
  ``lorenzo3d`` Pallas kernel's oracle.

Both produce *real archives* (zstd-entropy-coded code streams + literal
escapes) with a hard error bound: |rec - x| <= eb for every finite point.

Determinism contract: compression and decompression share the exact same
reconstruction code path (same jnp ops on the same values), so the encoder's
``rec`` equals the decoder's output bit-for-bit — required for NeurLZ, whose
enhancer is trained against the encoder-side reconstruction.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from . import codec, entropy
from ..kernels import dispatch
from ..obs import telemetry as obs
from .quantize import CODE_CAP, abs_bound_from_rel

_INTERNAL = jnp.float64 if jnp.array(0.0, jnp.float64).dtype == jnp.float64 else jnp.float32


@dataclasses.dataclass(frozen=True)
class SZLikeConfig:
    predictor: str = "interp"  # "interp" | "lorenzo"
    max_level: int = 4         # interp: number of refinement levels
    zstd_level: int = 9
    # Shrink the internal bound slightly so the final cast back to the input
    # dtype cannot push a point past the user bound.
    eb_margin: float = 1e-9


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

def _pad_to_lattice(x: np.ndarray, level: int) -> tuple[np.ndarray, tuple[int, ...]]:
    """Edge-pad every dim to ``D' ≡ 1 (mod 2^level)`` so all levels align."""
    s = 1 << level
    pads = []
    for d in x.shape:
        if d == 1:
            pads.append((0, 0))
        else:
            target = d if (d - 1) % s == 0 else ((d - 1) // s + 1) * s + 1
            pads.append((0, target - d))
    return np.pad(x, pads, mode="edge"), tuple(x.shape)


def _quantize_phase(values, pred, eb, out_dtype):
    """Fused quantize/reconstruct used by every phase (both directions).

    A point becomes a literal escape when (a) its code overflows, (b) it is
    non-finite, or (c) rounding the reconstruction to the *output dtype*
    would push it past the bound — (c) is what makes the bound hold exactly
    for fp32 fields even though internals run in fp64.
    """
    step = 2.0 * eb
    q = jnp.round((values - pred) / step)
    # non-finite *predictions* happen when a NaN literal sits among the
    # interpolation neighbors - escape those points too
    unpred = (jnp.abs(q) >= CODE_CAP) | ~jnp.isfinite(values) | ~jnp.isfinite(pred)
    codes = jnp.where(unpred, 0, q).astype(jnp.int32)
    rec = pred + codes.astype(pred.dtype) * step
    cast_bad = jnp.abs(rec.astype(out_dtype).astype(rec.dtype) - values) > eb
    unpred = unpred | cast_bad | ~jnp.isfinite(rec)
    codes = jnp.where(unpred, 0, codes)
    rec = jnp.where(unpred, values, rec)
    return codes, rec, unpred


def _encode_mask(mask: np.ndarray, level: int) -> dict:
    packed = np.packbits(mask.ravel())
    payload, cname = codec.compress(packed.tobytes(), level)
    return {"count": int(mask.size), "payload": payload, "codec": cname,
            "nbytes": len(payload)}


def _encode_streams(codes, mask, lits, config: SZLikeConfig) -> dict:
    """The archive's three host-coded streams: codes, escape mask,
    literal escapes."""
    return {"codes": entropy.encode_codes(codes, config.zstd_level),
            "unpred": _encode_mask(mask, config.zstd_level),
            "literals": entropy.encode_floats(lits, config.zstd_level)}


def _decode_mask(blob: dict) -> np.ndarray:
    raw = codec.decompress(blob["payload"], blob.get("codec", "zstd"))
    bits = np.unpackbits(np.frombuffer(raw, dtype=np.uint8))[: blob["count"]]
    return bits.astype(bool)


# ---------------------------------------------------------------------------
# interpolation predictor
# ---------------------------------------------------------------------------

def _phase_slicers(shape, axis, s):
    """Target/coarse slicers for one (level, axis) phase.

    Axes before ``axis`` are already refined to stride ``s//2`` this level;
    axes after are still at stride ``s``.
    """
    h = s // 2
    tgt, coarse = [], []
    for i, d in enumerate(shape):
        if d == 1:
            tgt.append(slice(0, 1))
            coarse.append(slice(0, 1))
        elif i < axis:
            tgt.append(slice(0, None, h))
            coarse.append(slice(0, None, h))
        elif i == axis:
            tgt.append(slice(h, None, s))
            coarse.append(slice(0, None, s))
        else:
            tgt.append(slice(0, None, s))
            coarse.append(slice(0, None, s))
    return tuple(tgt), tuple(coarse)


def _cubic_midpoint(coarse: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Cubic interpolation of midpoints from M+1 coarse points -> M preds.

    Interior midpoints use the 4-point cubic ``(-a + 9b + 9c - d) / 16``;
    the first/last fall back to linear — SZ3's boundary rule.
    """
    a = jnp.moveaxis(coarse, axis, 0)
    left1, right1 = a[:-1], a[1:]
    linear = 0.5 * (left1 + right1)
    m = a.shape[0] - 1  # number of midpoints
    if m >= 3:
        left2 = jnp.concatenate([a[:1], a[:-2]], axis=0)   # a[t-1] clamped
        right2 = jnp.concatenate([a[2:], a[-1:]], axis=0)  # a[t+2] clamped
        cubic = (-left2 + 9.0 * left1 + 9.0 * right1 - right2) / 16.0
        idx = jnp.arange(m).reshape((-1,) + (1,) * (a.ndim - 1))
        pred = jnp.where((idx == 0) | (idx == m - 1), linear, cubic)
    else:
        pred = linear
    return jnp.moveaxis(pred, 0, axis)


def _interp_schedule(shape: tuple[int, ...], max_level: int) -> tuple[int, list]:
    live = [d for d in shape if d > 1]
    if not live:
        return 1, []
    lmax = max(1, min(max_level, int(math.floor(math.log2(max(min(live) - 1, 2))))))
    phases = []
    for lev in range(lmax, 0, -1):
        s = 1 << lev
        for axis, d in enumerate(shape):
            if d > 1:
                phases.append((s, axis))
    return lmax, phases


def _interp_run(x: jnp.ndarray, eb: float, level: int, phases, mean: float,
                out_dtype=jnp.float32,
                codes_in: list | None = None, masks_in=None, lits_in=None,
                tel=obs.NULL):
    """Shared encode/decode walk.  Encode when ``codes_in is None``; each
    encode phase reads its codes, escape mask and target values back to
    the host (three reads counted on ``tel``)."""
    encode = codes_in is None
    # Coarsest lattice: predict the stored global mean.
    s0 = 1 << level
    init_slc = tuple(slice(0, 1) if d == 1 else slice(0, None, s0) for d in x.shape)
    rec = jnp.full(x.shape, jnp.asarray(mean, x.dtype), dtype=x.dtype)

    codes_out, masks_out, lits_out = [], [], []
    cursor = 0
    lit_cursor = 0

    def step(target_vals, pred, idx):
        nonlocal cursor, lit_cursor
        if encode:
            c, r, u = _quantize_phase(target_vals, pred, eb, out_dtype)
            un = obs.to_host(tel, u)
            codes_out.append(obs.to_host(tel, c).ravel())
            masks_out.append(un.ravel())
            lits_out.append(obs.to_host(tel, target_vals)[un].ravel())
            return r
        n = int(np.prod(pred.shape))
        c = jnp.asarray(codes_in[cursor:cursor + n].reshape(pred.shape))
        un = masks_in[cursor:cursor + n].reshape(pred.shape)
        cursor += n
        r = pred + c.astype(pred.dtype) * (2.0 * eb)
        k = int(un.sum())
        if k:
            # Patch literal escapes (host-side scatter keeps it deterministic).
            lv = lits_in[lit_cursor:lit_cursor + k]
            lit_cursor += k
            rn = np.array(r)  # writable copy
            rn[un] = lv
            r = jnp.asarray(rn)
        return r

    # coarsest lattice points
    tvals = x[init_slc]
    pred0 = rec[init_slc]
    r0 = step(tvals, pred0, -1)
    rec = rec.at[init_slc].set(r0)

    for s, axis in phases:
        tgt, coarse = _phase_slicers(x.shape, axis, s)
        pred = _cubic_midpoint(rec[coarse], axis)
        if int(np.prod(pred.shape)) == 0:
            continue
        tvals = x[tgt]
        r = step(tvals, pred, axis)
        rec = rec.at[tgt].set(r)

    if encode:
        return rec, (np.concatenate(codes_out) if codes_out else np.zeros(0, np.int32),
                     np.concatenate(masks_out) if masks_out else np.zeros(0, bool),
                     np.concatenate(lits_out) if lits_out else np.zeros(0, np.asarray(x).dtype))
    return rec, None


def _interp_encode_batched(xs: jnp.ndarray, ebs: np.ndarray, level: int,
                           phases, means: np.ndarray, out_dtype,
                           tel=obs.NULL):
    """Stacked-``[F, ...]`` mirror of :func:`_interp_run`'s encode branch.

    Runs the *same eager op sequence* as the per-field path with a leading
    field axis (per-field error bounds/means broadcast as ``[F, 1, ...]``).
    Elementwise jnp ops are bit-deterministic per element, so every field's
    slice of every phase equals the per-field run exactly — deliberately NOT
    jitted: fusing the float math can contract multiply-adds (FMA) and break
    the cross-engine byte-identity contract.

    Returns ``(rec [F, ...], [(codes, masks, lits)] per field)`` with the
    per-field streams concatenated in the per-field path's phase order.
    Host reads (three per phase, then the reconstruction) count on ``tel``.
    """
    nf = xs.shape[0]
    fshape = xs.shape[1:]
    bcast = (nf,) + (1,) * len(fshape)
    eb = jnp.asarray(np.asarray(ebs, np.float64).reshape(bcast))
    rec = jnp.broadcast_to(
        jnp.asarray(np.asarray(means, np.float64).reshape(bcast)).astype(xs.dtype),
        xs.shape)

    phase_codes, phase_masks, phase_lits = [], [], []

    def step(target_vals, pred):
        c, r, u = _quantize_phase(target_vals, pred, eb, out_dtype)
        un = obs.to_host(tel, u)
        vals = obs.to_host(tel, target_vals)
        phase_codes.append(obs.to_host(tel, c))
        phase_masks.append(un)
        # Extract each field's literal escapes now — retaining the full
        # target values until the end would pin an extra stacked-group copy.
        phase_lits.append([vals[f][un[f]].ravel() for f in range(nf)])
        return r

    s0 = 1 << level
    init_slc = (slice(None),) + tuple(
        slice(0, 1) if d == 1 else slice(0, None, s0) for d in fshape)
    r0 = step(xs[init_slc], rec[init_slc])
    rec = rec.at[init_slc].set(r0)

    for s, axis in phases:
        tgt, coarse = _phase_slicers(fshape, axis, s)
        tgt = (slice(None),) + tgt
        coarse = (slice(None),) + coarse
        pred = _cubic_midpoint(rec[coarse], axis + 1)
        if int(np.prod(pred.shape)) == 0:
            continue
        r = step(xs[tgt], pred)
        rec = rec.at[tgt].set(r)

    x_dtype = np.dtype(xs.dtype)
    streams = []
    for f in range(nf):
        codes = [c[f].ravel() for c in phase_codes]
        masks = [m[f].ravel() for m in phase_masks]
        lits = [pl[f] for pl in phase_lits]
        streams.append((
            np.concatenate(codes) if codes else np.zeros(0, np.int32),
            np.concatenate(masks) if masks else np.zeros(0, bool),
            np.concatenate(lits) if lits else np.zeros(0, x_dtype)))
    return obs.to_host(tel, rec), streams


def _interp_decode_batched(pad_shape, ebs: np.ndarray, level: int, phases,
                           means: np.ndarray, streams: list) -> np.ndarray:
    """Stacked-``[F, ...]`` mirror of :func:`_interp_run`'s decode branch.

    Same bit-stability discipline as :func:`_interp_encode_batched`: the
    exact per-field eager op sequence with a leading field axis and the
    per-field bounds/means broadcast as ``[F, 1, ...]`` — deliberately NOT
    jitted.  ``streams`` is the per-field ``(codes, masks, lits)`` decoded
    entropy streams; cursors advance in lockstep because every field shares
    the phase schedule.  Returns the stacked padded reconstruction.
    """
    nf = len(streams)
    bcast = (nf,) + (1,) * len(pad_shape)
    eb = jnp.asarray(np.asarray(ebs, np.float64).reshape(bcast))
    rec = jnp.broadcast_to(
        jnp.asarray(np.asarray(means, np.float64).reshape(bcast)).astype(_INTERNAL),
        (nf,) + tuple(pad_shape))

    cursor = 0
    lit_cursors = [0] * nf

    def step(pred):
        nonlocal cursor
        n = int(np.prod(pred.shape[1:]))
        c = jnp.asarray(np.stack(
            [streams[f][0][cursor:cursor + n].reshape(pred.shape[1:])
             for f in range(nf)]))
        un = np.stack(
            [streams[f][1][cursor:cursor + n].reshape(pred.shape[1:])
             for f in range(nf)])
        cursor += n
        r = pred + c.astype(pred.dtype) * (2.0 * eb)
        if un.any():
            rn = np.array(r)        # writable copy, host-side scatter
            for f in range(nf):
                k = int(un[f].sum())
                if k:
                    lv = streams[f][2][lit_cursors[f]:lit_cursors[f] + k]
                    lit_cursors[f] += k
                    rn[f][un[f]] = lv
            r = jnp.asarray(rn)
        return r

    s0 = 1 << level
    init_slc = (slice(None),) + tuple(
        slice(0, 1) if d == 1 else slice(0, None, s0) for d in pad_shape)
    r0 = step(rec[init_slc])
    rec = rec.at[init_slc].set(r0)

    for s, axis in phases:
        tgt, coarse = _phase_slicers(tuple(pad_shape), axis, s)
        tgt = (slice(None),) + tgt
        coarse = (slice(None),) + coarse
        pred = _cubic_midpoint(rec[coarse], axis + 1)
        if int(np.prod(pred.shape)) == 0:
            continue
        r = step(pred)
        rec = rec.at[tgt].set(r)
    return np.asarray(rec)


def decode_key(arc: dict) -> tuple:
    """Archives agreeing here may share one stacked decode dispatch (the
    registry ``decode_key`` capability).  Per-field error bounds are *not*
    part of the key — they broadcast along the stacked axis exactly as the
    encode side does, so one fused encode group always decodes fused too."""
    return (arc["predictor"], tuple(arc["shape"]), arc["dtype"],
            arc.get("level"), tuple(arc.get("pad_shape", ())))


def decompress_batched(arcs: list) -> list:
    """Decode a ``decode_key``-matched group as ONE stacked eager pass.

    Bit-identical to per-archive :func:`decompress` — the decode walk is
    elementwise per point, so running it with a leading ``[F]`` axis (codes
    stacked, per-field ``eb_int`` broadcast) reproduces every field's bits.
    """
    if not arcs:
        return []
    if any(a["kind"] != "szlike" for a in arcs):
        raise ValueError("not szlike archives")
    key = decode_key(arcs[0])
    if any(decode_key(a) != key for a in arcs):
        raise ValueError("decompress_batched needs decode_key-matched archives")
    nf = len(arcs)
    shape = tuple(arcs[0]["shape"])
    ebs = np.asarray([a["eb_int"] for a in arcs], np.float64)
    streams = [(entropy.decode_codes(a["codes"]).ravel(),
                _decode_mask(a["unpred"]),
                entropy.decode_floats(a["literals"]).ravel()) for a in arcs]

    if arcs[0]["predictor"] == "interp":
        pad_shape = tuple(arcs[0]["pad_shape"])
        level = arcs[0]["level"]
        _, phases = _interp_schedule(shape, level)
        means = np.asarray([a["mean"] for a in arcs], np.float64)
        rec = _interp_decode_batched(pad_shape, ebs, level, phases, means,
                                     streams)
        crop = tuple(slice(0, d) for d in shape)
        outs = [rec[f][crop] for f in range(nf)]
    else:
        d = jnp.asarray(np.stack(
            [streams[f][0].reshape(shape).astype(np.int32)
             for f in range(nf)]))
        q = lorenzo_undelta(d, axes=range(1, d.ndim))
        bcast = (nf,) + (1,) * len(shape)
        eb = jnp.asarray(ebs.reshape(bcast))
        rec = q.astype(_INTERNAL) * (2.0 * eb)
        out_all = np.array(rec)
        outs = []
        for f in range(nf):
            o = out_all[f]
            m = streams[f][1].reshape(shape)
            o[m] = streams[f][2]
            outs.append(o)
    # Always materialize per-field copies: the slices above are views into
    # the stacked [F, ...] array, and returning them would pin the whole
    # group's memory until the last field is dropped — defeating the
    # refcounted residency of the streaming decoder.  (astype with the
    # default copy=True detaches; same bits either way.)
    return [o.astype(np.dtype(a["dtype"])) for o, a in zip(outs, arcs)]


# ---------------------------------------------------------------------------
# Lorenzo (dual-quantization) predictor
# ---------------------------------------------------------------------------

def lorenzo_delta(q: jnp.ndarray, axes=None) -> jnp.ndarray:
    """N-D first-order Lorenzo delta of an integer lattice (zero boundary).

    Composition of first differences along every axis; exactly invertible by
    per-axis inclusive prefix sums in integer arithmetic.  ``axes`` restricts
    the differencing (the batched conv-stage passes ``range(1, ndim)`` so a
    stacked field axis is left alone); default is every axis.
    """
    d = q
    for axis in (range(q.ndim) if axes is None else axes):
        if q.shape[axis] == 1:
            continue
        shifted = jnp.concatenate(
            [jnp.zeros_like(jnp.take(d, jnp.arange(1), axis=axis)),
             jnp.take(d, jnp.arange(d.shape[axis] - 1), axis=axis)], axis=axis)
        d = d - shifted
    return d


def lorenzo_undelta(d: jnp.ndarray, axes=None) -> jnp.ndarray:
    q = d
    for axis in (range(d.ndim) if axes is None else axes):
        if d.shape[axis] == 1:
            continue
        q = jnp.cumsum(q, axis=axis, dtype=q.dtype)
    return q


# ---------------------------------------------------------------------------
# Lorenzo encode lowerings (repro.kernels.dispatch op "lorenzo")
# ---------------------------------------------------------------------------

def _lorenzo_encode_core(stacked, eb_arr, *, out_dtype: str):
    """Dual-quantization encode over a stacked ``[F, ...]`` group.

    The exact historical eager op sequence (prequant → escape detection →
    delta → reconstruction); per-field bounds broadcast as ``[F, 1, ...]``.
    Elementwise throughout and — deliberately — free of multiply-*add*
    chains (``rec`` is a bare ``codes * step`` product and the cast-check
    separates the product from the subtraction with dtype converts), so
    XLA has no FMA to contract and the jitted lowering below is
    byte-identical; the parity probe enforces rather than assumes this.
    Returns ``(delta int32, unpred bool, rec)``.
    """
    step = 2.0 * eb_arr
    q = jnp.round(stacked / step)
    unpred = (jnp.abs(q) >= CODE_CAP) | ~jnp.isfinite(stacked)
    qi = jnp.where(unpred, 0, q).astype(jnp.int32)
    rec = qi.astype(stacked.dtype) * step
    cast_bad = jnp.abs(rec.astype(jnp.dtype(out_dtype)).astype(rec.dtype)
                       - stacked) > eb_arr
    unpred = unpred | cast_bad
    qi = jnp.where(unpred, 0, qi)
    d = lorenzo_delta(qi, axes=range(1, qi.ndim))
    rec = jnp.where(unpred, stacked, qi.astype(stacked.dtype) * step)
    return d, unpred, rec


# Compiled variant: one dispatch per group instead of ~10 eager ops, input
# buffer donated (the stacked upload is dead after the call).  jax.jit's
# compile cache keys on (stacked shape, dtype, out_dtype, backend), so a
# snapshot's repeated same-shape groups compile once.
_lorenzo_encode_jit = functools.partial(
    jax.jit, static_argnames=("out_dtype",),
    donate_argnums=(0,))(_lorenzo_encode_core)


def lorenzo_jit_cache_size() -> int:
    """Compiled-variant cache entries (conv-stage stats / tests)."""
    return _lorenzo_encode_jit._cache_size()


def _lorenzo_jit_entry(stacked, eb_arr, *, out_dtype: str):
    with warnings.catch_warnings():
        # Donation is best-effort: XLA declines to alias when the input
        # stays live past its last read, and warns.  The decline is fine —
        # silence only that warning.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _lorenzo_encode_jit(stacked, eb_arr, out_dtype=out_dtype)


def _lorenzo_jit_probe() -> bool:
    """Byte-parity canary for the compiled encode: ragged odd shape, values
    at quantization boundaries, a CODE_CAP overflow, a NaN and an
    fp32-cast borderline — everything that could round differently if the
    compiler re-associated or contracted the float ops."""
    rng = np.random.default_rng(12345)
    x = np.cumsum(rng.standard_normal((2, 5, 7, 3)), axis=1).astype(np.float32)
    x[0, 0, 0, 0] = np.nan
    x[0, 1, 2, 0] = 3.0e9            # CODE_CAP overflow at eb=1e-3
    x[1, 2, 3, 1] = np.float32(2 ** 25) + 0.5   # cast-rounding boundary
    xj = jnp.asarray(x)
    eb = jnp.asarray(np.array([1e-3, 2e-2]).reshape(2, 1, 1, 1))
    want = _lorenzo_encode_core(xj, eb, out_dtype="float32")
    got = _lorenzo_jit_entry(jnp.asarray(x), eb, out_dtype="float32")
    return all(np.asarray(w).tobytes() == np.asarray(g).tobytes()
               for w, g in zip(want, got))


def _lorenzo_pallas_entry(stacked, eb_arr, *, out_dtype: str):
    """``lorenzo3d`` Pallas kernel wrapper (TPU target).  The kernel fuses
    prequant+delta+rec but has no escape semantics (CODE_CAP overflow,
    non-finite, cast-rounding literals), so escapes are recomputed around
    it; the parity probe decides whether the composition is byte-exact."""
    from ..kernels import ops as kernel_ops
    outs_d, outs_u, outs_r = [], [], []
    ebs = np.asarray(eb_arr).reshape(stacked.shape[0])
    for f in range(stacked.shape[0]):
        d, rec = kernel_ops.lorenzo_quantize(stacked[f], float(ebs[f]))
        _, unpred, _ = _lorenzo_encode_core(
            stacked[f][None], eb_arr[f][None], out_dtype=out_dtype)
        outs_d.append(d)
        outs_u.append(unpred[0])
        outs_r.append(rec)
    return (jnp.stack(outs_d), jnp.stack(outs_u), jnp.stack(outs_r))


def _lorenzo_pallas_probe() -> bool:
    return _probe_against_eager(_lorenzo_pallas_entry)


def _probe_against_eager(candidate) -> bool:
    rng = np.random.default_rng(99)
    x = np.cumsum(rng.standard_normal((1, 6, 5, 4)), axis=1).astype(np.float32)
    x[0, 0, 0, 0] = 4.0e9            # escape: the kernel has no CODE_CAP
    xj = jnp.asarray(x)
    eb = jnp.asarray(np.array([1e-3]).reshape(1, 1, 1, 1))
    want = _lorenzo_encode_core(xj, eb, out_dtype="float32")
    got = candidate(xj, eb, out_dtype="float32")
    return all(np.asarray(w).tobytes() == np.asarray(g).tobytes()
               for w, g in zip(want, got))


dispatch.register("lorenzo", "eager", _lorenzo_encode_core)
dispatch.register("lorenzo", "jit", _lorenzo_jit_entry,
                  probe=_lorenzo_jit_probe)
dispatch.register("lorenzo", "pallas", _lorenzo_pallas_entry,
                  probe=_lorenzo_pallas_probe, backends=("tpu",))


def _lorenzo_encode(stacked, eb_arr, out_dtype, lowering: str):
    impl, _ = dispatch.resolve("lorenzo", lowering)
    return impl(stacked, eb_arr, out_dtype=str(np.dtype(out_dtype)))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def compress(x: np.ndarray, rel_eb: float | None = None, *, abs_eb: float | None = None,
             config: SZLikeConfig = SZLikeConfig(),
             lowering: str = "auto",
             telemetry=obs.NULL) -> tuple[dict, np.ndarray]:
    """Compress ``x``; returns ``(archive, reconstruction)``.

    The reconstruction is exactly what :func:`decompress` will produce —
    NeurLZ trains its enhancer against it without a decode round-trip.

    ``lowering`` selects the Lorenzo quantize implementation through
    :mod:`repro.kernels.dispatch` (byte-identical archives either way — a
    variant that fails its parity probe falls back to eager).  The interp
    predictor is eager-only: its encode walks host-side entropy state
    between phases, so there is no jit variant to dispatch to.

    ``telemetry`` times the phase walk (``interp``) and the host entropy
    coding (``entropy``) and counts the transfers (:func:`obs.to_host`).
    """
    tel = telemetry
    x = np.asarray(x)
    if x.ndim not in (2, 3):
        raise ValueError(f"expected 2-D or 3-D field, got shape {x.shape}")
    orig_dtype = x.dtype
    if abs_eb is None:
        if rel_eb is None:
            raise ValueError("pass rel_eb or abs_eb")
        abs_eb = abs_bound_from_rel(x, rel_eb)
    eb_int = float(abs_eb) * (1.0 - config.eb_margin)

    work = x.astype(np.float64 if _INTERNAL == jnp.float64 else np.float32)
    finite = work[np.isfinite(work)]
    mean = float(finite.mean()) if finite.size else 0.0

    if config.predictor == "interp":
        level, phases = _interp_schedule(work.shape, config.max_level)
        padded, orig_shape = _pad_to_lattice(work, level)
        obs.count_h2d(tel, padded)
        xj = jnp.asarray(padded)
        with tel.span("interp"):
            rec, (codes, masks, lits) = _interp_run(
                xj, eb_int, level, phases, mean,
                out_dtype=jnp.dtype(orig_dtype), tel=tel)
            rec_np = obs.to_host(tel, rec)[tuple(slice(0, d)
                                                 for d in orig_shape)]
        arc = {
            "kind": "szlike", "predictor": "interp", "level": level,
            "shape": list(orig_shape), "pad_shape": list(padded.shape),
            "dtype": str(orig_dtype), "abs_eb": float(abs_eb), "eb_int": eb_int,
            "mean": mean,
        }
        with tel.span("entropy"):
            arc.update(_encode_streams(codes, masks, lits, config))
    elif config.predictor == "lorenzo":
        # One-field "group": the stacked [1, ...] op sequence is bitwise
        # the per-field one (elementwise ops; the size-1 leading axis is
        # skipped by the delta), which is the conv stage's byte-identity
        # contract — and it shares the dispatch-lowered encode.
        obs.count_h2d(tel, work)
        xj = jnp.asarray(work)[None]
        eb_arr = jnp.asarray(
            np.asarray([eb_int], np.float64).reshape((1,) + (1,) * work.ndim))
        d, unpred, rec = _lorenzo_encode(xj, eb_arr, orig_dtype, lowering)
        un_np = obs.to_host(tel, unpred)[0]
        rec_np = obs.to_host(tel, rec)[0]
        d_np = obs.to_host(tel, d)[0]
        arc = {
            "kind": "szlike", "predictor": "lorenzo",
            "shape": list(work.shape), "dtype": str(orig_dtype),
            "abs_eb": float(abs_eb), "eb_int": eb_int, "mean": mean,
        }
        with tel.span("entropy"):
            arc.update(_encode_streams(d_np, un_np.ravel(), work[un_np],
                                       config))
    else:
        raise ValueError(f"unknown predictor {config.predictor!r}")

    arc["nbytes"] = archive_nbytes(arc)
    return arc, rec_np.astype(orig_dtype, copy=False)


def compress_batched(xs, rel_eb: float | None = None, *,
                     abs_eb: float | None = None,
                     config: SZLikeConfig = SZLikeConfig(),
                     lowering: str = "auto", telemetry=obs.NULL) -> list:
    """Compress a group of same-shape/same-dtype fields in one stacked pass.

    The conv-stage batched entry point: the group's whole quantize +
    reconstruct runs as a single stacked-``[F, ...]`` op sequence (one
    device-op stream for the group instead of one per field); the host-side
    entropy stage stays per field.  Payloads are **byte-identical** to ``F``
    independent :func:`compress` calls — per-field bounds and means are
    derived exactly as the per-field path does and broadcast along the
    stacked axis.  Returns ``[(archive, reconstruction), ...]`` in order.

    ``lowering`` routes the stacked Lorenzo quantize through
    :mod:`repro.kernels.dispatch` exactly as :func:`compress` does —
    byte-identical payloads under every verdict.  ``telemetry`` records
    the same spans and transfer counts as :func:`compress`.
    """
    tel = telemetry
    arrs = [np.asarray(x) for x in xs]
    if not arrs:
        return []
    shape, dtype = arrs[0].shape, arrs[0].dtype
    if any(a.shape != shape or a.dtype != dtype for a in arrs):
        raise ValueError("compress_batched needs same-shape/same-dtype fields")
    if arrs[0].ndim not in (2, 3):
        raise ValueError(f"expected 2-D or 3-D fields, got shape {shape}")
    if abs_eb is None and rel_eb is None:
        raise ValueError("pass rel_eb or abs_eb")

    abs_ebs, eb_ints, means, works = [], [], [], []
    for a in arrs:
        ae = float(abs_eb) if abs_eb is not None else abs_bound_from_rel(a, rel_eb)
        abs_ebs.append(float(ae))
        eb_ints.append(float(ae) * (1.0 - config.eb_margin))
        w = a.astype(np.float64 if _INTERNAL == jnp.float64 else np.float32)
        finite = w[np.isfinite(w)]
        means.append(float(finite.mean()) if finite.size else 0.0)
        works.append(w)

    out = []
    if config.predictor == "interp":
        level, phases = _interp_schedule(shape, config.max_level)
        padded = [_pad_to_lattice(w, level)[0] for w in works]
        host = np.stack(padded)
        obs.count_h2d(tel, host)
        stacked = jnp.asarray(host)
        del host
        with tel.span("interp"):
            recs, streams = _interp_encode_batched(
                stacked, np.asarray(eb_ints), level, phases,
                np.asarray(means), jnp.dtype(dtype), tel=tel)
        crop = tuple(slice(0, d) for d in shape)
        for f in range(len(arrs)):
            arc = {
                "kind": "szlike", "predictor": "interp", "level": level,
                "shape": list(shape), "pad_shape": list(padded[f].shape),
                "dtype": str(dtype), "abs_eb": abs_ebs[f],
                "eb_int": eb_ints[f], "mean": means[f],
            }
            with tel.span("entropy"):
                arc.update(_encode_streams(*streams[f], config))
            arc["nbytes"] = archive_nbytes(arc)
            out.append((arc, recs[f][crop].astype(dtype, copy=False)))
    elif config.predictor == "lorenzo":
        host = np.stack(works)
        obs.count_h2d(tel, host)
        stacked = jnp.asarray(host)
        del host
        bcast = (len(arrs),) + (1,) * len(shape)
        eb_arr = jnp.asarray(np.asarray(eb_ints, np.float64).reshape(bcast))
        d, unpred, rec = _lorenzo_encode(stacked, eb_arr, dtype, lowering)
        d_np, un_np, rec_np = (obs.to_host(tel, a) for a in (d, unpred, rec))
        for f in range(len(arrs)):
            arc = {
                "kind": "szlike", "predictor": "lorenzo",
                "shape": list(shape), "dtype": str(dtype),
                "abs_eb": abs_ebs[f], "eb_int": eb_ints[f], "mean": means[f],
            }
            with tel.span("entropy"):
                arc.update(_encode_streams(d_np[f], un_np[f].ravel(),
                                           works[f][un_np[f]], config))
            arc["nbytes"] = archive_nbytes(arc)
            out.append((arc, rec_np[f].astype(dtype, copy=False)))
    else:
        raise ValueError(f"unknown predictor {config.predictor!r}")
    return out


def decompress(arc: dict) -> np.ndarray:
    if arc["kind"] != "szlike":
        raise ValueError("not an szlike archive")
    eb = arc["eb_int"]
    codes = entropy.decode_codes(arc["codes"]).ravel()
    masks = _decode_mask(arc["unpred"])
    lits = entropy.decode_floats(arc["literals"]).ravel()

    if arc["predictor"] == "interp":
        pad_shape = tuple(arc["pad_shape"])
        level = arc["level"]
        _, phases = _interp_schedule(tuple(arc["shape"]), level)
        dummy = jnp.zeros(pad_shape, dtype=_INTERNAL)
        rec, _ = _interp_run(dummy, eb, level, phases, arc["mean"],
                             codes_in=codes, masks_in=masks, lits_in=lits)
        out = np.array(rec)[tuple(slice(0, d) for d in arc["shape"])]
    else:
        d = jnp.asarray(codes.reshape(arc["shape"]).astype(np.int32))
        q = lorenzo_undelta(d)
        rec = q.astype(_INTERNAL) * (2.0 * eb)
        out = np.array(rec)
        m = masks.reshape(arc["shape"])
        out[m] = lits
    return out.astype(np.dtype(arc["dtype"]), copy=False)


def archive_nbytes(arc: dict) -> int:
    """Real archive size in bytes (payloads + small header estimate)."""
    n = 64  # header: shape/dtype/eb/mean/etc.
    for key in ("codes", "unpred", "literals"):
        if key in arc:
            n += arc[key]["nbytes"] + 16
    return n
