"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematically transparent version of its kernel; the
per-kernel tests sweep shapes/dtypes and ``assert_allclose`` kernel output
against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lorenzo3d_fwd_ref(x: jax.Array, eb: float) -> tuple[jax.Array, jax.Array]:
    """Prequant + separable 3-D Lorenzo delta (zero boundary)."""
    step = 2.0 * float(eb)
    q = jnp.round(x * (1.0 / step)).astype(jnp.int32)
    d = q
    for axis in range(3):
        zero = jnp.zeros_like(jax.lax.slice_in_dim(d, 0, 1, axis=axis))
        prev = jax.lax.slice_in_dim(d, 0, d.shape[axis] - 1, axis=axis)
        d = d - jnp.concatenate([zero, prev], axis=axis)
    rec = (q.astype(x.dtype) * step).astype(x.dtype)
    return d, rec


def lorenzo3d_inv_ref(d: jax.Array) -> jax.Array:
    q = d
    for axis in range(3):
        q = jnp.cumsum(q, axis=axis, dtype=jnp.int32)
    return q


def fused_enhance_ref(z, decomp, orig, eb: float, *, regulated: bool = True,
                      strict: bool = True):
    if regulated:
        resid = (2.0 * jax.nn.sigmoid(z.astype(jnp.float32)) - 1.0) * eb
    else:
        resid = z.astype(jnp.float32) * eb
    enh = (decomp.astype(jnp.float32) + resid).astype(decomp.dtype)
    bad = jnp.abs(enh.astype(jnp.float32) - orig.astype(jnp.float32)) > eb
    out = jnp.where(bad, decomp, enh) if strict else enh
    return out, bad.astype(jnp.uint8)
