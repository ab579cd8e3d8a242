"""The plain reference of the decoder's inference: the skipping DNN's
forward, the input assembly and the relaxed enhancement, written from the
paper (section 3.2.2, Fig. 8; regulation, section 3.3) and the archive's
own record of each field (its ``net``, ``stats``, ``aux`` and ``abs_eb``).

It imports nothing of the program's network, regulation or enhancement: it
reads a field's entry through ``Archive.entry`` and its weights through the
archive's weight unpacking, and nothing else the program made.

* Network (Fig. 8): a 3x3 input conv to ``widths[0]`` channels; four 3x3
  stride-2 convs to ``widths[1..4]``; four 3x3 stride-2 transpose convs
  back up (to ``widths[3]``, ``widths[2]``, ``widths[1]``, ``widths[1]``),
  each output concatenated with the encoder feature of its resolution when
  ``skip`` (upsampled channels first); a 3x3 output conv to one channel.
  ReLU after every layer but the last; SAME padding everywhere; the
  regulated head is ``2 sigmoid(z) - 1``.  Each conv is a
  ``lax.conv_general_dilated`` or ``lax.conv_transpose`` in float32 at
  ``Precision.HIGHEST``, under ``default_matmul_precision("highest")``.
* The archive's convention, which the paper leaves open: planes are padded
  at the bottom and the right to multiples of 16 by repeating the edge, and
  the output is cropped back.  Weights are ``[3, 3, C_in, C_out]`` with a
  bias per output channel, flattened in the sorted order of the layer names.
* Inputs: each channel (the field's conventional reconstruction, then its
  aux fields') normalized by the entry's ``stats`` in float64 numpy, one
  slice along the archive's ``slice_axis`` per sample.
* Relaxed enhancement: ``rec + abs_eb * out`` in float64 numpy.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_DN = ("NHWC", "HWIO", "NHWC")
_HIGHEST = jax.lax.Precision.HIGHEST

# Slices per forward call: one compiled shape, a few hundred MB at 512^2.
BLOCK = 10


def layer_shapes(c_in: int, widths, skip: bool = True) -> dict:
    """``name -> (3, 3, C_in, C_out)`` of the ten layers of Fig. 8."""
    c0, c1, c2, c3, c4 = widths
    cat = 2 if skip else 1
    return {
        "conv_in": (3, 3, c_in, c0),
        "down1": (3, 3, c0, c1),
        "down2": (3, 3, c1, c2),
        "down3": (3, 3, c2, c3),
        "down4": (3, 3, c3, c4),
        "up1": (3, 3, c4, c3),
        "up2": (3, 3, cat * c3, c2),
        "up3": (3, 3, cat * c2, c1),
        "up4": (3, 3, cat * c1, c1),
        "conv_out": (3, 3, c1 + c0 if skip else c1, 1),
    }


def params_from_entry(entry: dict) -> dict:
    """The enhancer's weights of one archived field, as float32 arrays."""
    from repro.core import archive as arc_io
    net = entry["net"]
    shapes = layer_shapes(int(net["c_in"]), tuple(net["widths"]),
                          bool(net["skip"]))
    like = {n: {"w": np.zeros(s, np.float32),
                "b": np.zeros((s[-1],), np.float32)}
            for n, s in shapes.items()}
    want = [list(s) for s in (like[n][k].shape for n in sorted(like)
                              for k in ("b", "w"))]
    if [list(s) for s in entry["weights"]["shapes"]] != want:
        raise ValueError(f"archived weight shapes {entry['weights']['shapes']}"
                         f" are not those of the net {net}")
    got = arc_io.unpack_weights(entry["weights"], like)
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), got)


def _conv(x, p, stride=1):
    y = jax.lax.conv_general_dilated(x, p["w"], (stride, stride), "SAME",
                                     dimension_numbers=_DN,
                                     precision=_HIGHEST)
    return y + p["b"]


def _deconv(x, p):
    y = jax.lax.conv_transpose(x, p["w"], (2, 2), "SAME",
                               dimension_numbers=_DN, precision=_HIGHEST)
    return y + p["b"]


@partial(jax.jit, static_argnames=("regulated", "skip"))
def forward(params, x, *, regulated: bool = True, skip: bool = True):
    """``x [N, H, W, C_in]`` float32 -> ``[N, H, W]`` float32."""
    n, h, w, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, (-h) % 16), (0, (-w) % 16), (0, 0)),
                mode="edge")
    relu = jax.nn.relu
    f0 = relu(_conv(x, params["conv_in"]))
    f1 = relu(_conv(f0, params["down1"], 2))
    f2 = relu(_conv(f1, params["down2"], 2))
    f3 = relu(_conv(f2, params["down3"], 2))
    f4 = relu(_conv(f3, params["down4"], 2))
    u = f4
    for name, enc in (("up1", f3), ("up2", f2), ("up3", f1), ("up4", f0)):
        u = relu(_deconv(u, params[name]))
        if skip:
            u = jnp.concatenate([u, enc], axis=-1)
    z = _conv(u, params["conv_out"])[:, :h, :w, 0]
    return 2.0 * jax.nn.sigmoid(z) - 1.0 if regulated else z


def inputs(entry: dict, rec: np.ndarray, aux: list, slice_axis: int
           ) -> np.ndarray:
    """``[N, H, W, C]`` float32: each channel minus its mean over its
    standard deviation (the entry's ``stats``), computed in float64."""
    chans = [rec] + list(aux)
    if len(chans) != len(entry["stats"]):
        raise ValueError(f"{len(chans)} channels for {len(entry['stats'])} "
                         f"stats")
    normed = [(np.moveaxis(np.asarray(c, np.float64), slice_axis, 0)
               - float(mu)) / float(sd)
              for c, (mu, sd) in zip(chans, entry["stats"])]
    return np.stack(normed, axis=-1).astype(np.float32)


def residual(entry: dict, x: np.ndarray, block: int = BLOCK) -> np.ndarray:
    """The network's output over every slice of ``x``, ``block`` slices per
    call (the last block padded with zero slices, so one shape compiles)."""
    params = params_from_entry(entry)
    net = entry["net"]
    n = x.shape[0]
    block = min(block, n)
    outs = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, n, block):
            xb = x[i:i + block]
            if xb.shape[0] < block:
                xb = np.concatenate(
                    [xb, np.zeros((block - xb.shape[0],) + xb.shape[1:],
                                  xb.dtype)])
            out = forward(params, jnp.asarray(xb),
                          regulated=bool(net["regulated"]),
                          skip=bool(net["skip"]))
            outs.append(np.asarray(out)[: min(block, n - i)])
    return np.concatenate(outs)


def replay(entry: dict, rec: np.ndarray, aux: list, slice_axis: int
           ) -> np.ndarray:
    """The field the archive entry decodes to under relaxed regulation, in
    float64: ``rec + abs_eb * out``."""
    if entry.get("degraded"):
        return np.asarray(rec, np.float64)
    if entry["mode"] != "relaxed" or not entry["learn_residual"]:
        raise ValueError(f"the reference replays relaxed residual entries, "
                         f"not mode {entry['mode']!r}")
    out = residual(entry, inputs(entry, rec, aux, slice_axis))
    return (np.asarray(rec, np.float64)
            + float(entry["abs_eb"]) * np.moveaxis(out.astype(np.float64),
                                                   0, slice_axis))
