"""Jit'd public wrappers around the Pallas kernels.

Handles padding to tile multiples, dtype plumbing, and the one place the
interpret-mode switch is decided: :func:`interpret_mode` compiles the
kernels with Mosaic on a TPU backend and runs their bodies through the
Pallas interpreter anywhere else (the CPU test suite).  The kernel entry
points take ``interpret`` as a required argument, so nothing reaches a
``pallas_call`` without this decision.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import dispatch
from .fused_enhance import fused_enhance
from .lorenzo3d import lorenzo3d_fwd, lorenzo3d_inv

# Mosaic's default scoped-VMEM limit on v5e.  The kernels are sized to fit
# it, so no kernel raises ``vmem_limit_bytes``.
VMEM_BUDGET = 16 * 2**20


def interpret_mode() -> bool:
    """True unless the (cached, test-forcible) backend is a TPU."""
    return dispatch.backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_tz(d: int, h: int, w: int, *, planes_per_tz: int,
             fixed_planes: int) -> int:
    """Largest power-of-two slab depth (≤ ``d``) whose double-buffered
    blocks — ``planes_per_tz * tz + fixed_planes`` (8, 128)-padded int32/f32
    planes — fit :data:`VMEM_BUDGET`.  Depths that are not a multiple get
    padded up by the wrappers and cropped after."""
    plane = _round_up(h, 8) * _round_up(w, 128) * 4
    tz = 1
    for cand in (2, 4, 8, 16, 32):
        if (cand <= d
                and (planes_per_tz * cand + fixed_planes) * plane
                <= VMEM_BUDGET):
            tz = cand
    return tz


def fwd_tz(d: int, h: int, w: int) -> int:
    """Slab depth of the forward kernel: in, out and rec slabs plus the
    halo plane, each double-buffered."""
    return _pick_tz(d, h, w, planes_per_tz=6, fixed_planes=2)


def inv_tz(d: int, h: int, w: int) -> int:
    """Slab depth of the inverse kernel: in and out slabs double-buffered,
    plus the carried z plane."""
    return _pick_tz(d, h, w, planes_per_tz=4, fixed_planes=1)


def _pad3(a, tz: int):
    """Zero-pad (D, H, W) at the high end to (tz, 8, 128) multiples.  Exact
    for both directions: a first difference and a prefix sum at index i
    read only indices ≤ i, so the padding never reaches the kept block."""
    d, h, w = a.shape
    pads = ((0, (-d) % tz), (0, (-h) % 8), (0, (-w) % 128))
    return jnp.pad(a, pads) if any(p for _, p in pads) else a


def lorenzo_quantize(x, eb: float):
    """Fused prequant + Lorenzo delta over a 3-D field.

    Returns (delta int32, rec float32) at the field's own shape.
    """
    x = jnp.asarray(x)
    if x.dtype == jnp.float64:
        x = x.astype(jnp.float32)  # kernel computes in fp32; rec returned fp32
    d0, h, w = x.shape
    tz = fwd_tz(d0, h, w)
    delta, rec = lorenzo3d_fwd(_pad3(x, tz), eb, tz=tz,
                               interpret=interpret_mode())
    return delta[:d0, :h, :w], rec[:d0, :h, :w]


def lorenzo_dequantize(delta, eb: float):
    """Inverse: delta codes -> reconstruction (q * 2eb)."""
    delta = jnp.asarray(delta, jnp.int32)
    d0, h, w = delta.shape
    tz = inv_tz(d0, h, w)
    q = lorenzo3d_inv(_pad3(delta, tz), tz=tz, interpret=interpret_mode())
    return q[:d0, :h, :w].astype(jnp.float32) * (2.0 * float(eb))


def enhance(z, decomp, orig, eb: float, *, regulated: bool = True,
            strict: bool = True):
    """Fused regulate+add+outlier over an N-D field; shapes all equal."""
    z = jnp.asarray(z, jnp.float32)
    decomp = jnp.asarray(decomp)
    orig = jnp.asarray(orig, decomp.dtype)
    shape = decomp.shape
    w = shape[-1]
    rows = int(np.prod(shape[:-1]))
    z2, d2, o2 = (a.reshape(rows, w) for a in (z, decomp, orig))
    # Row tiles are multiples of 32, the uint8 mask's sublane tile; five
    # (tr, W) blocks (three in, two out) double-buffered stay in budget.
    tr = 32
    for cand in (64, 128, 256):
        if cand <= rows and 2 * 5 * cand * _round_up(w, 128) * 4 \
                <= VMEM_BUDGET:
            tr = cand
    pad = (-rows) % tr
    if pad:
        # Elementwise op: zero rows compute garbage that is cropped below.
        z2, d2, o2 = (jnp.pad(a, ((0, pad), (0, 0))) for a in (z2, d2, o2))
    out, mask = fused_enhance(z2, d2, o2, eb, regulated=regulated,
                              strict=strict, tr=tr,
                              interpret=interpret_mode())
    return out[:rows].reshape(shape), mask[:rows].reshape(shape)
