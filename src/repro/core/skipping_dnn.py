"""The paper's lightweight *skipping DNN* enhancer (§3.2.2, Fig. 8).

Ten conv layers — four stride-2 down-samplings, four stride-2 up-samplings
with skip-connection concatenations, plus input/output convs — totalling
~3,073 parameters at ``c_in=1`` (the paper reports "a 10-layer network
requires only 3,000 parameters").  Pure-JAX pytree params; the forward pass
is `jit`/`vmap`/`shard_map`-friendly so thousands of per-block enhancers can
train simultaneously across a pod (DESIGN.md §3, batched block training).

Output heads (§3.3.2, Fig. 6):
  * ``regulated``   — Sigmoid squashed to ``(2σ(z)−1) ∈ (−1, 1)``; since the
    residual target is normalized by the error bound, the enhanced value can
    exactly reach the original (balanced regulation, Case B) while the total
    error stays ≤ 2×eb.
  * ``skip=False`` gives the non-skipping ablation of Fig. 4 (same depth).

Forward formulation (the bit-stable fast path)
----------------------------------------------
3×3 kernels over 1–16 channels are a poor fit for both of XLA's usual
lowerings.  Channels-last, the 1–16 channels sit on the TPU's 128 lanes
(8–128× padding) and every tap is a GEMM with K and N ≤ 16.  So the forward
runs channels-first, ``[C, N, H, W]``: one transpose in, one out, and the
plane's width sits on the lanes and its height on the sublanes.  Every conv
stacks its nine shifted windows on the channel axis in a fixed tap order
and contracts them in one f32 broadcast multiply and sum (:func:`_dot`);
every stride-2 transpose conv is its sub-pixel decomposition — four parity
planes, each one such contraction of its taps on the un-dilated grid.
Products and sums are plain f32 (no MXU pass, nothing in bfloat16), so the
result is at least as exact as a ``precision=HIGHEST`` GEMM, and one
reduction per layer in a fixed tap order makes the forward
**byte-identical** under eager, ``jit`` and grad, and under
``vmap``-over-fields wherever the backend lowers the gradient's reductions
alike with and without the field axis — the property the batched engine's
stacked strategy and the conv-stage jit path rely on
(tests/test_lowering.py, tests/test_skipping_dnn.py); the stacked strategy
is still gated by :func:`~repro.core.batched_engine.vmap_bit_parity`,
which the CPU passes and a v5e fails at 512² slices.  On a v5e one
training step at ``[10, 512, 512, 2]`` took 36.7 ms with these stacked taps
(cut as strided windows, before the parity split below) against 99.6 ms
with the channels-last ``HIGHEST`` GEMM taps they replaced.

Channels-first, a stride-2 window and a parity interleave are lane
shuffles, which the TPU does far below its memory bandwidth (a strided
window of a 512² map took ~0.8 ms on a v5e).  So a stride-2 conv splits its
padded input into parity planes once (:func:`_split`) and reads each of
its nine windows as a unit-stride slice of one plane, and a transpose conv
returns its four parity planes for the caller to interleave once
(:func:`_merge`).

The historical XLA formulation is kept as :func:`forward_reference` — the
accuracy oracle and the perf baseline; it is *not* bit-identical to
:func:`forward` (different contraction order), which is why PR 9 swapped the
formulation for every path at once instead of dispatching between them.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dispatch

_DN = ("CNHW", "HWIO", "CNHW")


@dataclasses.dataclass(frozen=True)
class SkippingDNNConfig:
    c_in: int = 1                 # 1 = single-field, >1 = cross-field channels
    widths: tuple = (4, 4, 6, 6, 8)   # conv_in + four encoder stages
    regulated: bool = True
    skip: bool = True
    dtype: str = "float32"

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


def _conv_param(key, kh, kw, cin, cout, dtype):
    wkey, _ = jax.random.split(key)
    fan_in = kh * kw * cin
    # Note: float(...) keeps the He scale weakly typed (x64 mode would
    # otherwise promote the whole kernel to float64).
    w = jax.random.normal(wkey, (kh, kw, cin, cout), dtype) * float(np.sqrt(2.0 / fan_in))
    return {"w": w.astype(dtype), "b": jnp.zeros((cout,), dtype)}


def init_params(key, cfg: SkippingDNNConfig):
    c0, c1, c2, c3, c4 = cfg.widths
    dt = cfg.jdtype
    keys = jax.random.split(key, 10)
    if cfg.skip:
        up_in = (c4, c3 + c3, c2 + c2, c1 + c1)  # after concat with encoder feature
        out_in = c1 + c0
    else:
        up_in = (c4, c3, c2, c1)
        out_in = c1
    return {
        "conv_in": _conv_param(keys[0], 3, 3, cfg.c_in, c0, dt),
        "down1": _conv_param(keys[1], 3, 3, c0, c1, dt),
        "down2": _conv_param(keys[2], 3, 3, c1, c2, dt),
        "down3": _conv_param(keys[3], 3, 3, c2, c3, dt),
        "down4": _conv_param(keys[4], 3, 3, c3, c4, dt),
        "up1": _conv_param(keys[5], 3, 3, up_in[0], c3, dt),
        "up2": _conv_param(keys[6], 3, 3, up_in[1], c2, dt),
        "up3": _conv_param(keys[7], 3, 3, up_in[2], c1, dt),
        "up4": _conv_param(keys[8], 3, 3, up_in[3], c1, dt),
        "conv_out": _conv_param(keys[9], 3, 3, out_in, 1, dt),
    }


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def stack_params(params_list):
    """Stack F same-structure enhancer trees into one tree with a leading
    field axis — the layout the batched engine trains under ``jax.vmap``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_params(stacked, num_fields: int):
    """Inverse of :func:`stack_params`: per-field trees (views, no copy)."""
    return [jax.tree.map(lambda x, i=i: x[i], stacked)
            for i in range(num_fields)]


# ---------------------------------------------------------------------------
# Fast bit-stable formulation: channels-first multiply-and-sum taps
# ---------------------------------------------------------------------------

def _dot(a, w):
    """Contract ``a``'s last axis with ``w``'s first: weights ``[C_out, K]``
    applied to stacked tap windows ``[K, N, H, W]`` give ``[C_out, N, H, W]``.
    Exact f32 products summed over the ``K`` axis in one reduction — no MXU
    pass, nothing in bfloat16."""
    a = a.reshape(a.shape + (1,) * (w.ndim - 1))
    return jnp.sum(a * w, axis=a.ndim - w.ndim)


def _split(x):
    """``[C, N, H, W]`` -> its four parity planes ``[C, 4, N, H/2, W/2]``,
    plane ``2*py + px`` holding ``x[..., py::2, px::2]``."""
    c, n, h, w = x.shape
    x = x.reshape(c, n, h // 2, 2, w // 2, 2)
    return jnp.transpose(x, (0, 3, 5, 1, 2, 4)).reshape(c, 4, n, h // 2,
                                                        w // 2)


def _merge(x):
    """Inverse of :func:`_split`."""
    c, _, n, h, w = x.shape
    x = jnp.transpose(x.reshape(c, 2, 2, n, h, w), (0, 3, 4, 1, 5, 2))
    return x.reshape(c, n, 2 * h, 2 * w)


def _conv(x, p, stride=1):
    """SAME 3×3 conv over ``x [C, N, H, W]``: the nine shifted windows
    stacked on the channel axis in a fixed tap order (``dy``, ``dx``,
    ``C_in``), one ``_dot``.  A stride-2 conv splits its padded input into
    parity planes once (:func:`_split`), so each window is a unit-stride
    slice of one plane (tap ``dy`` reads parity ``dy % 2`` from row
    ``dy // 2``) rather than a strided slice of the lanes."""
    w, b = p["w"], p["b"]
    cin, n, h, wd = x.shape
    cout = w.shape[-1]
    ho = (h + stride - 1) // stride
    wo = (wd + stride - 1) // stride

    def pads(size, out):
        lo = max((out - 1) * stride + 3 - size, 0) // 2
        # stride 2 reads 2 * out + 1 rows: one more makes the count even
        return lo, (out + 2 if stride == 1 else 2 * out + 2) - size - lo

    xp = jnp.pad(x, ((0, 0), (0, 0), pads(h, ho), pads(wd, wo)))
    taps = [(dy, dx) for dy in range(3) for dx in range(3)]
    if stride == 2:
        xp = _split(xp)
        wins = [xp[:, 2 * (dy % 2) + dx % 2, :, dy // 2:dy // 2 + ho,
                   dx // 2:dx // 2 + wo] for dy, dx in taps]
    else:
        wins = [xp[:, :, dy:dy + ho, dx:dx + wo] for dy, dx in taps]
    # [C_out, (dy, dx, C_in)]: the windows' order
    wt = jnp.transpose(w, (3, 0, 1, 2)).reshape(cout, 9 * cin)
    return _dot(wt, jnp.concatenate(wins, axis=0)) + b[:, None, None, None]


def _deconv(x, p):
    """Stride-2 SAME 3×3 transpose conv over ``x [C, N, H, W]`` via its
    sub-pixel decomposition, returned as parity planes
    ``[C_out, 4, N, H, W]`` (:func:`_merge` interleaves them).

    ``conv_transpose(k=3, s=2, SAME)`` ≡ zero-dilate + pad (2, 1) + VALID
    conv with the unflipped kernel, so output row ``2i+py`` only sees input
    rows through kernel taps ``dy ∈ {py, py+2} ∩ [0, 2]`` — each parity
    plane is one ``_dot`` of its 1, 2 or 4 tap windows on the *small* grid.
    """
    w, b = p["w"], p["b"]
    cin, n, h, wd = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    wt = jnp.transpose(w, (0, 1, 3, 2))    # each tap a [C_out, C_in] matrix
    planes = []
    for py in range(2):
        ytaps = [(py, py - 1)] + ([(py + 2, py)] if py + 2 <= 2 else [])
        for px in range(2):
            xtaps = [(px, px - 1)] + ([(px + 2, px)] if px + 2 <= 2 else [])
            taps = [(dy, dx, my, mx) for dy, my in ytaps for dx, mx in xtaps]
            wins = [jax.lax.slice(xp, (0, 0, my + 1, mx + 1),
                                  (cin, n, my + 1 + h, mx + 1 + wd))
                    for _, _, my, mx in taps]
            planes.append(_dot(
                jnp.concatenate([wt[dy, dx] for dy, dx, _, _ in taps], 1),
                jnp.concatenate(wins, axis=0)) + b[:, None, None, None])
    return jnp.stack(planes, axis=1)


# ---------------------------------------------------------------------------
# Historical XLA formulation — accuracy oracle + perf baseline
# ---------------------------------------------------------------------------

def _conv_xla(x, p, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=_DN, precision=jax.lax.Precision.HIGHEST)
    return y + p["b"][:, None, None, None]


def _deconv_xla(x, p):
    y = jax.lax.conv_transpose(
        x, p["w"], strides=(2, 2), padding="SAME", dimension_numbers=_DN,
        precision=jax.lax.Precision.HIGHEST)
    return _split(y + p["b"][:, None, None, None])


def _forward_core(params, x, *, regulated, skip, conv, deconv):
    """x: [N, H, W, C_in] normalized decompressed slices -> [N, H, W, 1]
    normalized residual prediction.  Runs channels-first inside,
    ``[C, N, H, W]`` (one transpose in, one out); H, W are padded to
    multiples of 16 (replicate edges) and cropped back."""
    n, h, w, _ = x.shape
    x = jnp.transpose(x, (3, 0, 1, 2))
    ph, pw = (-h) % 16, (-w) % 16
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, ph), (0, pw)), mode="edge")

    act = jax.nn.relu
    f0 = act(conv(x, params["conv_in"]))          # H
    f1 = act(conv(f0, params["down1"], stride=2))  # H/2
    f2 = act(conv(f1, params["down2"], stride=2))  # H/4
    f3 = act(conv(f2, params["down3"], stride=2))  # H/8
    f4 = act(conv(f3, params["down4"], stride=2))  # H/16

    u = act(_merge(deconv(f4, params["up1"])))     # H/8
    if skip:
        u = jnp.concatenate([u, f3], axis=0)
    u = act(_merge(deconv(u, params["up2"])))      # H/4
    if skip:
        u = jnp.concatenate([u, f2], axis=0)
    u = act(_merge(deconv(u, params["up3"])))      # H/2
    if skip:
        u = jnp.concatenate([u, f1], axis=0)
    u = act(_merge(deconv(u, params["up4"])))      # H
    if skip:
        u = jnp.concatenate([u, f0], axis=0)
    z = conv(u, params["conv_out"])                # [1,N,H,W]

    if regulated:
        out = 2.0 * jax.nn.sigmoid(z) - 1.0        # (−1, 1): balanced 2×eb regulation
    else:
        out = z
    return jnp.transpose(out[:, :, :h, :w], (1, 2, 3, 0))


@partial(jax.jit, static_argnames=("regulated", "skip"))
def _forward_fast(params, x, *, regulated: bool = True, skip: bool = True):
    return _forward_core(params, x, regulated=regulated, skip=skip,
                         conv=_conv, deconv=_deconv)


@partial(jax.jit, static_argnames=("regulated", "skip"))
def forward_reference(params, x, *, regulated: bool = True,
                      skip: bool = True):
    """The XLA-conv forward at ``precision=HIGHEST``.  Numerically
    ~1e-6-close to :func:`forward` but NOT bit-identical; kept as the
    accuracy oracle and the ``kernel/dnn_forward`` bench baseline."""
    return _forward_core(params, x, regulated=regulated, skip=skip,
                         conv=_conv_xla, deconv=_deconv_xla)


def forward(params, x, *, regulated: bool = True, skip: bool = True,
            lowering: str = "auto"):
    """Skipping-DNN forward under the requested lowering.

    ``eager`` and ``jit`` are the *same* compiled bit-stable fast
    formulation (f32 multiply-and-sum taps in a fixed accumulation order),
    so the eager/jit byte-identity half of the contract holds
    structurally.
    There is no Pallas variant: this forward runs inside the training
    step's ``value_and_grad``, so every lowering must be differentiable,
    and ``pallas``/``auto`` resolve to this same formulation.  Traceable:
    resolution happens at trace time, so callers may close over a fixed
    ``lowering`` inside their own jit/scan.
    """
    if lowering in ("eager", "jit"):
        return _forward_fast(params, x, regulated=regulated, skip=skip)
    impl, _ = dispatch.resolve("dnn_forward", lowering)
    return impl(params, x, regulated=regulated, skip=skip)


dispatch.register("dnn_forward", "eager", _forward_fast)
dispatch.register("dnn_forward", "jit", _forward_fast)


def apply(params, x, cfg: SkippingDNNConfig):
    return forward(params, x, regulated=cfg.regulated, skip=cfg.skip)
