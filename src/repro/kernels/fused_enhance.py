"""Fused enhancement + error regulation — Pallas TPU kernel (paper §3.3).

Decode-side hot path, fused into one VMEM pass per tile:

    r̂        = (2·σ(z) − 1) · eb          (balanced 2× regulation, Fig. 6B)
    enhanced  = decomp + r̂
    outlier   = |enhanced − orig| > eb      (encode side only)
    final     = outlier ? decomp : enhanced (strict 1× mode, Fig. 5)

Unfused, this is four elementwise HBM round-trips over ≥512² planes; fused
it reads (z, decomp, orig) once and writes (final, mask) once — the op is
purely bandwidth-bound, so the fusion is the whole win.  The same kernel
serves decode (orig := decomp makes the mask all-False and ``final`` the
relaxed-mode enhancement).

Tiling: elementwise over (rows, cols) tiles of the flattened-to-2D field;
the row tile is sized to VMEM by ops.py and is a multiple of 32 (the uint8
mask's sublane tile), with the column dimension kept at the field's full W
so any width is a legal block.  The index map returns ``np.int32``
constants: under ``jax_enable_x64`` a bare Python ``0`` is an i64 block
index, which Mosaic refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_Z = np.int32(0)


def _kernel(z_ref, dec_ref, orig_ref, out_ref, mask_ref, *, eb: float,
            regulated: bool, strict: bool):
    z = z_ref[...]
    dec = dec_ref[...]
    orig = orig_ref[...]
    if regulated:
        resid = (2.0 * jax.nn.sigmoid(z.astype(jnp.float32)) - 1.0) * eb
    else:
        resid = z.astype(jnp.float32) * eb
    enh = (dec.astype(jnp.float32) + resid).astype(dec.dtype)
    bad = jnp.abs(enh.astype(jnp.float32) - orig.astype(jnp.float32)) > eb
    if strict:
        out_ref[...] = jnp.where(bad, dec, enh)
    else:
        out_ref[...] = enh
    mask_ref[...] = bad.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("eb", "regulated", "strict", "tr", "interpret"))
def fused_enhance(z: jax.Array, decomp: jax.Array, orig: jax.Array, eb: float,
                  *, regulated: bool = True, strict: bool = True, tr: int,
                  interpret: bool) -> tuple[jax.Array, jax.Array]:
    """All inputs (R, W) 2-D (ops.py reshapes/pads fields).  Returns
    (final same-dtype-as-decomp, outlier mask uint8)."""
    rows, cols = z.shape
    if rows % tr:
        raise ValueError(f"{rows} rows are not a multiple of tr={tr}")
    kernel = functools.partial(_kernel, eb=float(eb), regulated=regulated,
                               strict=strict)
    spec = pl.BlockSpec((tr, cols), lambda i: (i, _Z))
    return pl.pallas_call(
        kernel,
        grid=(rows // tr,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(decomp.shape, decomp.dtype),
            jax.ShapeDtypeStruct(decomp.shape, jnp.uint8),
        ],
        interpret=interpret,
    )(z, decomp, orig)
