"""Seeded scientific-field snapshots made on the device.

A jnp port of the statistics of ``src/repro/data/fields.py``: each dataset
family is a shared latent Gaussian random field plus one field-specific
component per field, mixed with coupling ``c`` (the cross-field correlation
that NeurLZ's cross-field channels exploit), then mapped to the family's
value range.  The spectra, mixing weights and transforms are those of the
host generator; the random draws are ``jax.random`` draws, so the values
are not the host generator's bytes.  Everything runs in float32 /
complex64 in one jitted call per snapshot, so a 512x512-plane snapshot
takes milliseconds of device time instead of minutes of host FFT.

The snapshots of one seed form a stream that evolves as a running
simulation's output does: every Gaussian component of snapshot ``k`` is
``cos(k step) A + sin(k step) B`` for two fixed draws ``A`` and ``B``, so
successive snapshots differ at every point while the spectrum, the
variance and with them the value range stay close.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

DATASET_FIELDS = {
    "nyx": ("temperature", "dark_matter_density", "baryon_density",
            "velocity_y"),
    "hurricane": ("cloud", "precip", "w"),
}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, including ones that do not fit
    in 32 bits (the low and high words are folded in separately)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _amplitude(shape, slope, aniso):
    """Spectral amplitude ``k^(-slope/2)`` (power ~ k^-slope), DC zeroed."""
    ks = [jnp.fft.fftfreq(n, dtype=jnp.float32) * n for n in shape]
    if aniso is not None:
        ks = [k * a for k, a in zip(ks, aniso)]
    grids = jnp.meshgrid(*ks, indexing="ij", sparse=True)
    k2 = sum(g * g for g in grids)
    k2 = jnp.where(k2 == 0, 1.0, k2)
    amp = k2 ** (-slope / 4.0)
    return amp.at[(0,) * len(shape)].set(0.0)


def _grf(key, shape, slope, aniso):
    """Unit-variance Gaussian random field with power spectrum ~ k^-slope."""
    kr, ki = jax.random.split(key)
    noise = jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                            jax.random.normal(ki, shape, jnp.float32))
    spec = jnp.fft.fftn(noise) * _amplitude(shape, slope, aniso)
    f = jnp.real(jnp.fft.ifftn(spec))
    f = f - f.mean()
    sd = f.std()
    return f / jnp.where(sd > 0, sd, 1.0)


def _turning(key, shape, slope, aniso, *, angle):
    """``cos(angle)`` times one unit-variance draw plus ``sin(angle)`` times
    an independent one: again a unit-variance field of the same spectrum."""
    ka, kb = jax.random.split(key)
    return (jnp.cos(angle) * _grf(ka, shape, slope, aniso)
            + jnp.sin(angle) * _grf(kb, shape, slope, aniso))


def _nyx(key, shape, c, n, grf):
    keys = jax.random.split(key, 5)
    latent = grf(keys[0], shape, 3.0, None)

    def mix(i, slope):
        return (jnp.sqrt(c) * latent
                + jnp.sqrt(1.0 - c) * grf(keys[i], shape, slope, None))

    out = [jnp.exp(1.2 * mix(1, 3.0)) * 1e4]                  # K-like
    if n > 1:
        dmd = jnp.exp(2.0 * mix(2, 2.8))                      # overdensity
        out.append(dmd)
    if n > 2:
        out.append(jnp.exp(2.2 * (c * jnp.log(jnp.maximum(dmd, 1e-6)) / 2.0
                                  + (1 - c) * mix(3, 2.6))))
    if n > 3:
        out.append(mix(4, 3.2) * 2.5e7)                       # cm/s-like
    return out


def _hurricane(key, shape, c, n, grf):
    aniso = (4.0, 1.0, 1.0)     # stratified atmosphere: steep vertical spectrum
    keys = jax.random.split(key, 4)
    latent = grf(keys[0], shape, 2.6, aniso)

    def mix(i, slope):
        return (jnp.sqrt(c) * latent
                + jnp.sqrt(1.0 - c) * grf(keys[i], shape, slope, aniso))

    out = [jnp.maximum(mix(1, 2.6) - 0.8, 0.0) * 1e-3]         # sparse, spiky
    if n > 1:
        out.append(jnp.maximum(mix(2, 2.4) - 1.0, 0.0) * 5e-3)
    if n > 2:
        out.append(mix(3, 2.9) * 8.0)
    return out


_GENERATORS = {"nyx": _nyx, "hurricane": _hurricane}


@partial(jax.jit, static_argnames=("dataset", "shape", "n"))
def _snapshot(key, angle, *, dataset, shape, n, coupling):
    grf = partial(_turning, angle=angle)
    fields = _GENERATORS[dataset](key, shape, coupling, n, grf)
    return tuple(f.astype(jnp.float32) for f in fields)


def snapshot(dataset: str, shape, names, seed: int, index: int = 0,
             coupling: float = 0.8,
             step: float = 0.05) -> dict[str, np.ndarray]:
    """Snapshot ``index`` of the stream drawn from ``seed``, ``step``
    radians on from the one before: the named fields (a prefix of the
    dataset's field order) as host float32 arrays.  The same ``(seed,
    index, step)`` gives the same bytes on the same backend."""
    order = DATASET_FIELDS[dataset]
    names = tuple(names)
    if names != order[:len(names)]:
        raise ValueError(f"{dataset} fields must be a prefix of {order}, "
                         f"got {names}")
    c = float(np.clip(coupling, 0.0, 1.0))
    arrs = _snapshot(seed_key(seed), jnp.float32(float(step) * index),
                     dataset=dataset, shape=tuple(int(s) for s in shape),
                     n=len(names), coupling=jnp.float32(c))
    return {name: np.asarray(a) for name, a in zip(names, arrs)}
