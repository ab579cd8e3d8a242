"""Synthetic scientific-field generators, statistically matched to the
paper's datasets (SDRBench originals are not redistributable offline).

Each generator builds correlated multi-field blocks from a *shared latent*
Gaussian random field plus field-specific components — mirroring how Nyx's
Temperature / Dark-Matter-Density / Baryon-Density are coupled through the
same governing equations (§3.4), which is exactly what cross-field learning
exploits.  Spectral slopes and value-range transforms per dataset family:

  nyx       — cosmology: log-normal density fields (huge dynamic range, like
              Baryon Density's 4.8e6 range), power-law spectrum k^-3
  miranda   — large turbulence, FP64, smooth k^-5/3 Kolmogorov-like spectra
  hurricane — weather: anisotropic (stratified) spectra, FP32
"""
from __future__ import annotations

import numpy as np


def _k2(shape, aniso) -> np.ndarray:
    """Squared (optionally anisotropic) wavenumber grid, DC set to 1."""
    kfreqs = [np.fft.fftfreq(n) * n for n in shape]
    grids = np.meshgrid(*kfreqs, indexing="ij", sparse=True)
    if aniso:
        grids = [g * a for g, a in zip(grids, aniso)]
    k2 = sum(g ** 2 for g in grids)
    k2[(0,) * len(shape)] = 1.0
    return k2


def _grf(rng: np.random.Generator, shape, slope: float,
         aniso: tuple = None, k2: np.ndarray | None = None) -> np.ndarray:
    """Gaussian random field with isotropic power spectrum ~ k^-slope.

    ``k2`` reuses a wavenumber grid from :func:`_k2` (same shape/aniso)
    across calls; the values are the same either way."""
    if k2 is None:
        k2 = _k2(shape, aniso)
    amp = k2 ** (-slope / 4.0)  # power ~ k^-slope  => amplitude ~ k^-slope/2
    amp[(0,) * len(shape)] = 0.0
    # Built in place: bit-identical to ``a + 1j * b`` for finite a, b != 0.
    noise = np.empty(shape, np.complex128)
    noise.real = rng.standard_normal(shape)
    noise.imag = rng.standard_normal(shape)
    spec = np.fft.fftn(noise)
    del noise
    spec *= amp
    del amp
    f = np.fft.ifftn(spec).real
    f -= f.mean()
    sd = f.std()
    return f / (sd if sd > 0 else 1.0)


def _nyx(rng, shape, c):
    w_shared, w_own = np.sqrt(c), np.sqrt(1.0 - c)
    k2 = _k2(shape, None)
    latent = _grf(rng, shape, slope=3.0, k2=k2)
    def mix(slope):
        return w_shared * latent + w_own * _grf(rng, shape, slope, k2=k2)
    yield "temperature", (np.exp(1.2 * mix(3.0)) * 1e4).astype(np.float32)  # K-like
    dmd = (np.exp(2.0 * mix(2.8))).astype(np.float32)               # overdensity
    yield "dark_matter_density", dmd
    yield "baryon_density", (np.exp(2.2 * (c * np.log(np.maximum(dmd, 1e-6)) / 2.0
                                           + (1 - c) * mix(2.6)))).astype(np.float32)
    yield "velocity_y", (mix(3.2) * 2.5e7).astype(np.float32)      # cm/s-like


def _miranda(rng, shape, c):
    w_shared, w_own = np.sqrt(c), np.sqrt(1.0 - c)
    latent = _grf(rng, shape, slope=5.0 / 3.0 + 2.0)  # smooth turbulence
    def mix(slope):
        return w_shared * latent + w_own * _grf(rng, shape, slope)
    diff = (1.0 + 0.3 * mix(3.6)).astype(np.float64)
    yield "diffusivity", diff
    yield "viscosity", (1.0 + 0.25 * (c * (diff - 1.0) / 0.3
                                      + (1 - c) * mix(3.5))).astype(np.float64)
    yield "velocity_z", (mix(3.7) * 0.8).astype(np.float64)


def _hurricane(rng, shape, c):
    w_shared, w_own = np.sqrt(c), np.sqrt(1.0 - c)
    aniso = (4.0, 1.0, 1.0)  # stratified atmosphere: steep vertical spectrum
    latent = _grf(rng, shape, slope=2.6, aniso=aniso)
    def mix(slope):
        return w_shared * latent + w_own * _grf(rng, shape, slope, aniso=aniso)
    yield "cloud", np.maximum(mix(2.6) - 0.8, 0.0).astype(np.float32) * 1e-3  # sparse/spiky
    yield "precip", np.maximum(mix(2.4) - 1.0, 0.0).astype(np.float32) * 5e-3
    yield "w", (mix(2.9) * 8.0).astype(np.float32)


_GENERATORS = {"nyx": _nyx, "miranda": _miranda, "hurricane": _hurricane}


def make_fields(dataset: str = "nyx", shape=(64, 64, 64), seed: int = 0,
                coupling: float = 0.8, names=None) -> dict[str, np.ndarray]:
    """Correlated multi-field block for one synthetic dataset.

    ``coupling`` sets the shared-latent fraction (cross-field correlation).
    ``names`` restricts the result to those fields: generation stops after
    the last of them in :data:`DATASET_FIELDS` order, and the values are
    the same bytes the full call returns (the fields come from one RNG
    stream in that order).
    """
    if dataset not in _GENERATORS:
        raise ValueError(f"unknown dataset {dataset!r}")
    want = set(DATASET_FIELDS[dataset] if names is None else names)
    unknown = want - set(DATASET_FIELDS[dataset])
    if unknown:
        raise ValueError(f"{dataset} has no fields {sorted(unknown)}")
    c = float(np.clip(coupling, 0.0, 1.0))
    out = {}
    for name, field in _GENERATORS[dataset](np.random.default_rng(seed),
                                            shape, c):
        if name in want:
            out[name] = field
            if len(out) == len(want):
                break
    return out


def snapshot_specs(num_fields: int, shape=(16, 32, 32), dataset: str = "nyx",
                   seed0: int = 2) -> dict[str, dict]:
    """Lazy per-field recipes for a ``num_fields``-field snapshot.

    Names match :func:`benchmarks.common.snapshot_fields` exactly
    (``{field}_s{seed}`` over successive seed blocks), but nothing is
    generated here — :func:`load_spec` materializes one field at a time, so
    the streaming pipeline can ingest snapshots far larger than memory
    (``repro.streaming.source.synthetic_snapshot_source`` wraps this)."""
    specs: dict[str, dict] = {}
    seed = seed0
    while len(specs) < num_fields:
        for name in DATASET_FIELDS[dataset]:
            if len(specs) < num_fields:
                specs[f"{name}_s{seed}"] = {"dataset": dataset,
                                            "shape": tuple(shape),
                                            "seed": seed, "field": name}
        seed += 1
    return specs


def load_spec(spec: dict) -> np.ndarray:
    """Materialize one snapshot field from its recipe.

    Regenerates only that field's seed block (the shared-latent coupling
    means a block's fields come from one RNG pass), so transient memory is
    one block regardless of snapshot size.  Deterministic: repeated loads
    return identical bytes, as the streaming source contract requires."""
    return make_fields(spec["dataset"], shape=spec["shape"],
                       seed=spec["seed"], names=(spec["field"],))[spec["field"]]


DATASET_DTYPES = {"nyx": "float32", "miranda": "float64", "hurricane": "float32"}
DATASET_FIELDS = {
    "nyx": ["temperature", "dark_matter_density", "baryon_density", "velocity_y"],
    "miranda": ["diffusivity", "viscosity", "velocity_z"],
    "hurricane": ["cloud", "precip", "w"],
}
# Cross-field partner map used by benchmarks (paper §3.4/§5.2: T predicted
# with DMD help; baryon with DMD; etc.).
DEFAULT_CROSS_FIELD = {
    "nyx": {"temperature": ("dark_matter_density",),
            "baryon_density": ("dark_matter_density",),
            "dark_matter_density": ("temperature",),
            "velocity_y": ("temperature",)},
    "miranda": {"diffusivity": ("viscosity",), "viscosity": ("diffusivity",),
                "velocity_z": ("diffusivity",)},
    "hurricane": {"cloud": ("w",), "precip": ("cloud",), "w": ("precip",)},
}
