"""Quality arithmetic, kept with the benchmark so no PR can change it.

Copied from the program's ``repro.core.metrics`` (PSNR, SDRBench
value-range convention) and ``benchmarks/common.py`` (max |err| / eb).
All arithmetic is float64 on the host.
"""
from __future__ import annotations

import numpy as np


def psnr(orig: np.ndarray, rec: np.ndarray) -> float:
    """Value-range PSNR in dB over the finite points of ``orig``."""
    o = np.asarray(orig, dtype=np.float64)
    r = np.asarray(rec, dtype=np.float64)
    finite = np.isfinite(o)
    o, r = o[finite], r[finite]
    if o.size == 0:
        return float("nan")
    vrange = o.max() - o.min()
    if vrange == 0:
        vrange = max(abs(o.max()), 1.0)
    mse = np.mean((o - r) ** 2)
    if mse == 0:
        return float("inf")
    return float(20.0 * np.log10(vrange) - 10.0 * np.log10(mse))


def mse_over_eb2(orig: np.ndarray, rec: np.ndarray, eb: float) -> float:
    """Mean squared error over the finite points of ``orig``, in units of
    ``eb**2``, so fields of different value ranges weigh alike."""
    o = np.asarray(orig, dtype=np.float64)
    r = np.asarray(rec, dtype=np.float64)
    if r.shape != o.shape:
        return float("inf")
    finite = np.isfinite(o)
    if not finite.any():
        return 0.0
    return float(np.mean((r[finite] - o[finite]) ** 2) / (eb * eb))


def gain_db(mse_conv: float, mse_rec: float) -> float:
    """How far a reconstruction's error lies below the conventional
    stage's alone, in dB (0 when they are the same, negative when worse)."""
    if mse_rec == 0.0:
        return float("inf") if mse_conv > 0.0 else 0.0
    if not np.isfinite(mse_rec):
        return float("-inf")
    return float(10.0 * np.log10(mse_conv / mse_rec))


def abs_bound(x: np.ndarray, rel_eb: float) -> float:
    """The absolute error bound a relative bound means for ``x``: SZ3's
    ``-M REL`` semantics, ``rel_eb`` times the finite value range as the
    field's own dtype computes ``max - min``."""
    x = np.asarray(x)
    finite = x[np.isfinite(x)]
    if finite.size == 0:
        return float(rel_eb)
    vrange = float(finite.max() - finite.min())
    if vrange == 0.0:
        vrange = max(abs(float(finite.max())), 1.0)
    return float(rel_eb) * vrange


def max_err_over_eb(orig: np.ndarray, rec: np.ndarray, eb: float) -> float:
    """Worst |rec - orig| over the finite points of ``orig``, in units of
    ``eb``.  A shape mismatch or a non-finite reconstruction of a finite
    point reads as infinity."""
    o = np.asarray(orig, dtype=np.float64)
    r = np.asarray(rec)
    if r.shape != o.shape:
        return float("inf")
    r = r.astype(np.float64)
    finite = np.isfinite(o)
    if not finite.any():
        return 0.0
    err = np.abs(r[finite] - o[finite])
    if not np.isfinite(err).all():
        return float("inf")
    return float(err.max() / eb)
