"""Operations and bytes from shapes, for rooflines and MFU.

Model FLOPs count the multiply-adds of the convolutions (2 FLOPs each) as
the network defines them; bias adds, activations and the optimizer are
left out.  Bytes are what an algorithm must read and write at the least:
its inputs once and its outputs once.
"""
from __future__ import annotations

import math

F32 = 4


def _pad16(n: int) -> int:
    return n + (-n) % 16


def dnn_layers(h: int, w: int, c_in: int, widths=(4, 4, 6, 6, 8),
               skip: bool = True) -> list[tuple[str, int]]:
    """Multiply-adds of each layer of the skipping DNN's forward for one
    ``h x w`` sample with ``c_in`` channels, in layer order.  The network
    pads its planes to multiples of 16 and computes on the padded plane;
    the single-channel output conv counts one output channel."""
    c0, c1, c2, c3, c4 = widths
    hp, wp = _pad16(h), _pad16(w)

    def conv(hin, win, cin, cout, stride):
        ho, wo = math.ceil(hin / stride), math.ceil(win / stride)
        return 9 * cin * cout * ho * wo

    def deconv(hin, win, cin, cout):       # every input pixel meets 9 taps
        return 9 * cin * cout * hin * win

    up_in = ((c4, 2 * c3, 2 * c2, 2 * c1) if skip else (c4, c3, c2, c1))
    out_in = c1 + c0 if skip else c1
    s = [hp // (1 << k) for k in range(5)], [wp // (1 << k) for k in range(5)]
    hs, ws = s
    return [
        ("conv_in", conv(hs[0], ws[0], c_in, c0, 1)),
        ("down1", conv(hs[0], ws[0], c0, c1, 2)),
        ("down2", conv(hs[1], ws[1], c1, c2, 2)),
        ("down3", conv(hs[2], ws[2], c2, c3, 2)),
        ("down4", conv(hs[3], ws[3], c3, c4, 2)),
        ("up1", deconv(hs[4], ws[4], up_in[0], c3)),
        ("up2", deconv(hs[3], ws[3], up_in[1], c2)),
        ("up3", deconv(hs[2], ws[2], up_in[2], c1)),
        ("up4", deconv(hs[1], ws[1], up_in[3], c1)),
        ("conv_out", conv(hs[0], ws[0], out_in, 1, 1)),
    ]


def dnn_forward_flops(h, w, c_in, widths=(4, 4, 6, 6, 8), skip=True) -> int:
    return 2 * sum(m for _, m in dnn_layers(h, w, c_in, widths, skip))


def dnn_train_flops(h, w, c_in, widths=(4, 4, 6, 6, 8), skip=True) -> int:
    """Forward plus backward for one sample: the backward computes every
    layer's weight gradient (one forward's work each) and every layer's
    input gradient except the first layer's (its input is data)."""
    layers = dnn_layers(h, w, c_in, widths, skip)
    fwd = sum(m for _, m in layers)
    return 2 * (fwd + fwd + (fwd - layers[0][1]))


def dnn_forward_bytes(h, w, c_in) -> int:
    """Read one normalized ``c_in``-channel sample, write one residual."""
    return F32 * h * w * (c_in + 1)


def dnn_train_bytes(h, w, c_in) -> int:
    """Read one sample and its target (the weights are a few KB)."""
    return F32 * h * w * (c_in + 1)


def trained_samples(n_slices: int, epochs: int, batch: int) -> int:
    """Samples one field's online training runs: ``epochs`` epochs of
    ``n // b`` drop-last batches of ``b = min(batch, n)`` slices."""
    b = min(batch, n_slices)
    return epochs * max(1, n_slices // b) * b


# The SZ-like interpolation quantizer, per predicted point: the 4-point
# cubic midpoint (2 multiplies, 3 adds, 1 scale), the quantization
# (subtract, scale, round, multiply-add back) and the bound check on the
# cast reconstruction (subtract, abs, compare).
INTERP_FLOPS_PER_POINT = 14


def interp_quantizer_least(points: int, value_bytes: int = F32
                           ) -> tuple[int, int]:
    """``(flops, bytes)`` the interpolation quantizer needs for ``points``
    values: read each value once, write its int32 code and its
    reconstruction once."""
    return (INTERP_FLOPS_PER_POINT * points,
            points * (value_bytes + 4 + value_bytes))


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Roofline least time in seconds and the bound that sets it."""
    t_c = flops / peak["flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
