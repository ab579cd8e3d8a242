"""Fused pre-quantization + 3-D Lorenzo delta — Pallas TPU kernel.

The compression hot loop (DESIGN.md §3): cuSZ's dual-quantization turns SZ's
sequential Lorenzo recurrence into a pure stencil, and this kernel fuses the
two memory-bound passes —

    q = round(x / (2*eb))          (prequant to the error-bound lattice)
    d = Δx Δy Δz q                 (8-point first-order Lorenzo delta)

— into a single HBM→VMEM pass, plus a fused reconstruction output
``rec = q * 2*eb`` (what the decompressor will see; NeurLZ trains against
it).  An unfused jnp pipeline writes q to HBM and re-reads it with shifted
gathers; at 512³ fp32 that is several× the traffic of this kernel.

Tiling: the grid walks z-slabs of ``tz`` planes; y/x stay at full extent in
VMEM (planes arrive padded to the (8, 128) int32/f32 tile by ops.py, so a
512² plane is 1 MiB).  The one-plane z halo is the input array bound a
second time with a *one-plane* block whose index map points at the plane
just before the slab — no host-side padding copy; the kernel masks z=0.
Every block is double-buffered, so the forward holds 2·(3·tz + 1) planes
and the inverse 2·2·tz + 1 (its carry plane) — the figures Mosaic reports
for these kernels, which ops.py sizes ``tz`` against.

In-plane shifts are lane/sublane rotations (``pltpu.roll``) with the
wrapped row/column masked to zero, not unaligned concatenations, which
Mosaic cannot lower.  The inverse (``undelta``) is three prefix sums: the
x and y scans are log-step (Hillis-Steele) roll-and-add ladders — exact,
since int32 addition wraps identically in any order — and the z scan walks
the slab's planes with a VMEM scratch plane carrying the running sum across
grid steps (the TPU grid is a sequential loop), so it is one pass.

Index maps return ``np.int32`` constants: under ``jax_enable_x64`` (which
the package turns on) a bare Python ``0`` becomes an i64 block index and
Mosaic refuses the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_Z = np.int32(0)


def _shift1(a, axis: int):
    """``a`` moved one step up ``axis`` (a lane/sublane axis), zero-filled
    at index 0 — the in-plane neighbour of a first difference."""
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    return jnp.where(idx == _Z, jnp.zeros_like(a), pltpu.roll(a, np.int32(1), axis))


def _prefix_sum(a, axis: int):
    """Inclusive int32 prefix sum along a lane/sublane ``axis``:
    ceil(log2 n) roll-and-add steps (Mosaic has no cumsum)."""
    n = a.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    zero = jnp.zeros_like(a)
    s = 1
    while s < n:
        a = a + jnp.where(idx >= np.int32(s), pltpu.roll(a, np.int32(s), axis), zero)
        s *= 2
    return a


def _fwd_kernel(x_ref, xprev_ref, d_ref, rec_ref, *, inv_step: float,
                step: float):
    """One z-slab: prequant + separable Lorenzo delta (+ reconstruction)."""
    zi = pl.program_id(0)
    q = jnp.round(x_ref[...] * inv_step).astype(jnp.int32)

    # z-neighbour plane: the plane before the slab (zero at z=0).
    qp = jnp.round(xprev_ref[...] * inv_step).astype(jnp.int32)
    qp = jnp.where(zi == _Z, jnp.zeros_like(qp), qp)

    # Separable first differences Δz, Δy, Δx (their composition is the
    # 8-point Lorenzo stencil; order is irrelevant).
    d = q - jnp.concatenate([qp, q[:-1]], axis=0) if q.shape[0] > 1 \
        else q - qp
    d = d - _shift1(d, 1)
    d = d - _shift1(d, 2)

    d_ref[...] = d
    rec_ref[...] = q.astype(rec_ref.dtype) * step


def _inv_kernel(d_ref, q_ref, carry_ref):
    """One z-slab of the inverse: prefix sums over x, y, then z with a
    carried plane."""
    @pl.when(pl.program_id(0) == _Z)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    s = _prefix_sum(_prefix_sum(d_ref[...], 2), 1)
    plane = carry_ref[...]
    for k in range(s.shape[0]):
        plane = plane + s[k]
        q_ref[k] = plane
    carry_ref[...] = plane


@functools.partial(jax.jit, static_argnames=("eb", "tz", "interpret"))
def lorenzo3d_fwd(x: jax.Array, eb: float, *, tz: int, interpret: bool
                  ) -> tuple[jax.Array, jax.Array]:
    """Fused prequant+delta.  ``x``: (D, H, W) float32 with D divisible by
    ``tz`` (ops.py pads); returns (delta int32, rec float32)."""
    dsz, h, w = x.shape
    if dsz % tz:
        raise ValueError(f"depth {dsz} is not a multiple of tz={tz}")
    step = 2.0 * float(eb)
    kernel = functools.partial(_fwd_kernel, inv_step=1.0 / step, step=step)
    slab = pl.BlockSpec((tz, h, w), lambda i: (i, _Z, _Z))
    return pl.pallas_call(
        kernel,
        grid=(dsz // tz,),
        in_specs=[
            slab,
            # Same array, the one plane before the slab (clamped at 0; the
            # kernel masks z=0).
            pl.BlockSpec((1, h, w),
                         lambda i: (jnp.maximum(i * tz - 1, 0), _Z, _Z)),
        ],
        out_specs=[slab, slab],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.int32),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        ],
        interpret=interpret,
    )(x, x)


@functools.partial(jax.jit, static_argnames=("tz", "interpret"))
def lorenzo3d_inv(d: jax.Array, *, tz: int, interpret: bool) -> jax.Array:
    """Inverse delta: int32 lattice codes back from the delta stream."""
    dsz, h, w = d.shape
    if dsz % tz:
        raise ValueError(f"depth {dsz} is not a multiple of tz={tz}")
    slab = pl.BlockSpec((tz, h, w), lambda i: (i, _Z, _Z))
    return pl.pallas_call(
        _inv_kernel,
        grid=(dsz // tz,),
        in_specs=[slab],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct(d.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((h, w), jnp.int32)],
        interpret=interpret,
    )(d)
