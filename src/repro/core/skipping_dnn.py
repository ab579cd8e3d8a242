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
These convs are XLA's worst case: 3×3 kernels over 1–16 channels lower to
``conv_general_dilated`` programs that run ~3 GFLOP/s on CPU.  The forward
here instead expresses every conv as an accumulation of nine shifted
``jax.lax.dot_general`` contractions (one GEMM per kernel tap) and every
stride-2 transpose conv as its sub-pixel decomposition — four parity planes,
each a small accumulation of taps on the un-dilated grid, interleaved back.
All contractions are pinned to ``precision=HIGHEST``, additions happen in a
fixed tap order, and single-output-channel convs are padded to two columns
(a ``(K, 1)`` GEMV re-associates under ``vmap`` where a ``(K, 2)`` GEMM does
not), which makes the forward **byte-identical** under eager, ``jit``,
``vmap``-over-fields and grad — the property the batched engine's stacked
strategy and the conv-stage jit path rely on (tests/test_lowering.py).
It is also 2–3× faster than the XLA conv lowering on CPU (bench_kernels
``kernel/dnn_forward`` row).

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

_DN = ("NHWC", "HWIO", "NHWC")
_P = jax.lax.Precision.HIGHEST


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
# Fast bit-stable formulation: convs as accumulated shifted GEMMs
# ---------------------------------------------------------------------------

def _dot(a, w):
    """Contract ``a``'s channel axis with ``w [cin, cout]``; one GEMM,
    precision pinned so the reduction is never FMA-contracted or split."""
    return jax.lax.dot_general(a, w, (((a.ndim - 1,), (0,)), ((), ())),
                               precision=_P)


def _conv_taps(x, w, b, stride):
    """SAME 3×3 conv as nine shifted ``_dot`` accumulations, fixed tap order."""
    n, h, wd, cin = x.shape
    ho = (h + stride - 1) // stride
    wo = (wd + stride - 1) // stride

    def pads(size, out):
        total = max((out - 1) * stride + 3 - size, 0)
        lo = total // 2
        return lo, total - lo

    ylo, yhi = pads(h, ho)
    xlo, xhi = pads(wd, wo)
    xp = jnp.pad(x, ((0, 0), (ylo, yhi), (xlo, xhi), (0, 0)))
    acc = None
    for dy in range(3):
        for dx in range(3):
            win = jax.lax.slice(
                xp, (0, dy, dx, 0),
                (n, dy + (ho - 1) * stride + 1, dx + (wo - 1) * stride + 1,
                 cin),
                (1, stride, stride, 1))
            t = _dot(win, w[dy, dx])
            acc = t if acc is None else acc + t
    return acc + b


def _conv(x, p, stride=1):
    w, b = p["w"], p["b"]
    cout = w.shape[-1]
    if cout == 1:
        # A (K, 1) contraction lowers to a GEMV whose batched form under
        # vmap re-associates the reduction; a zero-padded (K, 2) GEMM lowers
        # identically in both — the sole source of vmap bit-divergence.
        w = jnp.concatenate([w, jnp.zeros_like(w)], axis=-1)
        b = jnp.concatenate([b, jnp.zeros_like(b)])
        return _conv_taps(x, w, b, stride)[..., :1]
    return _conv_taps(x, w, b, stride)


def _deconv(x, p):
    """Stride-2 SAME 3×3 transpose conv via sub-pixel decomposition.

    ``conv_transpose(k=3, s=2, SAME)`` ≡ zero-dilate + pad (2, 1) + VALID
    conv with the unflipped kernel, so output row ``2i+py`` only sees input
    rows through kernel taps ``dy ∈ {py, py+2} ∩ [0, 2]`` — each parity
    plane is a tiny accumulation on the *small* grid, interleaved back.
    """
    w, b = p["w"], p["b"]
    n, h, wd, cin = x.shape
    cout = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = jnp.zeros((n, 2 * h, 2 * wd, cout), x.dtype)
    for py in range(2):
        ytaps = [(py, py - 1)] + ([(py + 2, py)] if py + 2 <= 2 else [])
        for px in range(2):
            xtaps = [(px, px - 1)] + ([(px + 2, px)] if px + 2 <= 2 else [])
            acc = None
            for dy, my in ytaps:
                for dx, mx in xtaps:
                    win = jax.lax.slice(xp, (0, my + 1, mx + 1, 0),
                                        (n, my + 1 + h, mx + 1 + wd, cin))
                    t = _dot(win, w[dy, dx])
                    acc = t if acc is None else acc + t
            out = out.at[:, py::2, px::2, :].set(acc)
    return out + b


# ---------------------------------------------------------------------------
# Historical XLA formulation — accuracy oracle + perf baseline
# ---------------------------------------------------------------------------

def _conv_xla(x, p, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=_DN)
    return y + p["b"]


def _deconv_xla(x, p):
    y = jax.lax.conv_transpose(
        x, p["w"], strides=(2, 2), padding="SAME", dimension_numbers=_DN)
    return y + p["b"]


def _forward_core(params, x, *, regulated, skip, conv, deconv):
    """x: [N, H, W, C_in] normalized decompressed slices -> [N, H, W, 1]
    normalized residual prediction.  H, W are padded to multiples of 16
    internally (replicate edges) and cropped back."""
    n, h, w, _ = x.shape
    ph, pw = (-h) % 16, (-w) % 16
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (0, ph), (0, pw), (0, 0)), mode="edge")

    act = jax.nn.relu
    f0 = act(conv(x, params["conv_in"]))          # H
    f1 = act(conv(f0, params["down1"], stride=2))  # H/2
    f2 = act(conv(f1, params["down2"], stride=2))  # H/4
    f3 = act(conv(f2, params["down3"], stride=2))  # H/8
    f4 = act(conv(f3, params["down4"], stride=2))  # H/16

    u = act(deconv(f4, params["up1"]))             # H/8
    if skip:
        u = jnp.concatenate([u, f3], axis=-1)
    u = act(deconv(u, params["up2"]))              # H/4
    if skip:
        u = jnp.concatenate([u, f2], axis=-1)
    u = act(deconv(u, params["up3"]))              # H/2
    if skip:
        u = jnp.concatenate([u, f1], axis=-1)
    u = act(deconv(u, params["up4"]))              # H
    if skip:
        u = jnp.concatenate([u, f0], axis=-1)
    z = conv(u, params["conv_out"])                # [N,H,W,1]

    if regulated:
        out = 2.0 * jax.nn.sigmoid(z) - 1.0        # (−1, 1): balanced 2×eb regulation
    else:
        out = z
    if ph or pw:
        out = out[:, :h, :w, :]
    return out


@partial(jax.jit, static_argnames=("regulated", "skip"))
def _forward_fast(params, x, *, regulated: bool = True, skip: bool = True):
    return _forward_core(params, x, regulated=regulated, skip=skip,
                         conv=_conv, deconv=_deconv)


@partial(jax.jit, static_argnames=("regulated", "skip"))
def forward_reference(params, x, *, regulated: bool = True,
                      skip: bool = True):
    """The pre-PR9 XLA-conv forward.  Numerically ~1e-6-close to
    :func:`forward` but NOT bit-identical; kept as the accuracy oracle and
    the ``kernel/dnn_forward`` bench baseline."""
    return _forward_core(params, x, regulated=regulated, skip=skip,
                         conv=_conv_xla, deconv=_deconv_xla)


def forward(params, x, *, regulated: bool = True, skip: bool = True,
            lowering: str = "auto"):
    """Skipping-DNN forward under the requested lowering.

    ``eager`` and ``jit`` are the *same* compiled bit-stable fast
    formulation (it is jit-safe by construction — HIGHEST-precision GEMMs
    in a fixed accumulation order leave XLA nothing to contract), so the
    eager/jit byte-identity half of the contract holds structurally.
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
