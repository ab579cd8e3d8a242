"""Pluggable conventional-compressor registry.

The old dispatch was an if/elif chain over hardcoded names, and
``archive_nbytes`` silently fell through to the zfplike accounting for any
archive kind it did not recognize.  This module replaces both with explicit
registration: a compressor registers its name, capability metadata and entry
points once, and every engine (serial / batched / streaming) resolves it
through the same table.  Third-party compressors become a
:func:`register` call instead of a core edit:

    from repro.compressors import registry

    registry.register(registry.CompressorEntry(
        name="mylz", kind="mylz",
        compress=my_compress,          # (x, rel_eb, *, abs_eb=None, **kw)
        decompress=my_decompress,      # (arc) -> np.ndarray
        archive_nbytes=my_nbytes,      # (arc) -> int
    ))

Capability metadata drives the batched conventional stage
(:mod:`repro.core.conv_stage`): an entry that provides
``compress_batched`` declares that compressing a stacked ``[F, ...]``
group of same-shape/same-dtype fields yields payloads **byte-identical**
to ``F`` per-field calls (the bit-stable-lowering contract — conventional
archives must match across engines).  Entries without it always run
per-field.

Archive *kinds* are registered separately from compressor names because
several compressors may share an archive format (``szlike`` and
``szlike-lorenzo`` both emit ``kind="szlike"``); decode-side dispatch
(``decompress`` / ``archive_nbytes``) goes by the archive's ``kind`` tag.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class CompressorEntry:
    """One registered conventional compressor.

    ``compress(x, rel_eb, *, abs_eb=None, **kw) -> (archive_dict, rec)``
    must uphold the determinism contract: the returned reconstruction is
    bit-identical to what ``decompress(archive_dict)`` produces (NeurLZ
    trains its enhancer against the encoder-side reconstruction).

    ``compress_batched(xs, rel_eb, *, abs_eb=None) -> list[(arc, rec)]``
    (optional) takes a stacked ``[F, ...]`` array of same-shape/same-dtype
    fields and must return per-field archives whose payloads are
    byte-identical to ``F`` independent ``compress`` calls — the capability
    that unlocks the fused conv-stage group dispatch.

    ``decompress_batched(arcs) -> list[np.ndarray]`` (optional, the
    symmetric decode capability) takes archives that agree on
    ``decode_key`` and must return reconstructions **bit-identical** to one
    ``decompress`` call per archive, produced by the same stacked eager-op
    sequence discipline as the encode side (no jit — FMA contraction would
    change float bits).  ``decode_key(arc)`` is the hashable an archive
    must match on to share a stacked decode dispatch (shape/dtype plus any
    layout fields like the predictor or interpolation level; the per-field
    error bound rides along as a broadcast vector, exactly as it does on
    the encode side).
    """

    name: str
    kind: str                                # archive "kind" tag it emits
    compress: Callable
    decompress: Callable
    archive_nbytes: Callable
    compress_batched: Callable | None = None
    decompress_batched: Callable | None = None
    decode_key: Callable | None = None       # (arc) -> hashable group key
    dtypes: tuple = ("float32", "float64")   # dtypes the batched path covers
    deterministic: bool = True               # encoder rec == decoder output
    description: str = ""

    @property
    def batchable(self) -> bool:
        return self.compress_batched is not None

    @property
    def decode_batchable(self) -> bool:
        return (self.decompress_batched is not None
                and self.decode_key is not None)

    def batch_supports(self, dtype) -> bool:
        return self.batchable and str(np.dtype(dtype)) in self.dtypes

    def decode_batch_supports(self, arc: dict) -> bool:
        return (self.decode_batchable
                and str(np.dtype(arc.get("dtype", "float32"))) in self.dtypes)


_COMPRESSORS: dict[str, CompressorEntry] = {}
_KINDS: dict[str, CompressorEntry] = {}


def register(entry: CompressorEntry, *, overwrite: bool = False) -> CompressorEntry:
    """Register a compressor (and its archive kind, if new).

    Entries sharing an archive ``kind`` must agree on the decode-side entry
    points — the first registration of a kind owns its ``decompress`` /
    ``archive_nbytes`` dispatch.
    """
    if entry.name in _COMPRESSORS and not overwrite:
        raise ValueError(f"compressor {entry.name!r} already registered "
                         "(pass overwrite=True to replace)")
    owner = _KINDS.get(entry.kind)
    if owner is not None and owner.name != entry.name and (
            owner.decompress is not entry.decompress
            or owner.archive_nbytes is not entry.archive_nbytes
            or owner.decompress_batched is not entry.decompress_batched
            or owner.decode_key is not entry.decode_key):
        raise ValueError(
            f"archive kind {entry.kind!r} is owned by {owner.name!r} with "
            "different decode entry points (incl. decompress_batched/"
            "decode_key); kinds must decode unambiguously")
    _COMPRESSORS[entry.name] = entry
    if owner is None or owner.name == entry.name:
        _KINDS[entry.kind] = entry
    return entry


def unregister(name: str) -> None:
    entry = _COMPRESSORS.pop(name, None)
    if entry is not None and _KINDS.get(entry.kind) is entry:
        # Hand the kind to any remaining entry that shares it.
        del _KINDS[entry.kind]
        for other in _COMPRESSORS.values():
            if other.kind == entry.kind:
                _KINDS[entry.kind] = other
                break


def get(name: str) -> CompressorEntry:
    try:
        return _COMPRESSORS[name]
    except KeyError:
        raise ValueError(
            f"unknown compressor {name!r} (registered: {sorted(_COMPRESSORS)})"
        ) from None


def for_archive(arc: dict) -> CompressorEntry:
    """Resolve the entry owning an archive dict's ``kind`` tag."""
    kind = arc.get("kind")
    try:
        return _KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown archive kind {kind!r} (registered: {sorted(_KINDS)})"
        ) from None


def names() -> list[str]:
    return sorted(_COMPRESSORS)


def entries() -> list[CompressorEntry]:
    return [_COMPRESSORS[n] for n in names()]


# ---------------------------------------------------------------------------
# Dispatch helpers (the public compressors.* API routes through these)
# ---------------------------------------------------------------------------

def compress(x, rel_eb=None, *, abs_eb=None, compressor="szlike", **kw):
    return get(compressor).compress(x, rel_eb, abs_eb=abs_eb, **kw)


def decompress(arc: dict):
    return for_archive(arc).decompress(arc)


def archive_nbytes(arc: dict) -> int:
    # No fall-through: an unknown kind is a hard error (it used to be
    # silently accounted with the zfplike layout).
    return for_archive(arc).archive_nbytes(arc)


class DecodeStats:
    """Thread-safe accounting of conventional-decode dispatches.

    Hand an instance to :func:`decompress_many` (``stats=``) and it records
    how the call actually executed: how many stacked
    ``decompress_batched`` dispatches ran (``batched``), how many archives
    decoded one at a time (``single``), the total archives decoded and the
    widest stacked dispatch seen.  The serving tier's coalescing guarantee
    — *N same-signature requests execute as one stacked dispatch* — is
    asserted against these numbers (tests and the ``bench_serving`` smoke
    guard), so the counters are part of the dispatch contract, not just
    telemetry.
    """

    __slots__ = ("_lock", "batched", "single", "archives", "max_width")

    def __init__(self):
        self._lock = threading.Lock()
        self.batched = 0        # stacked decompress_batched dispatches
        self.single = 0         # per-archive decompress calls
        self.archives = 0       # total archives decoded
        self.max_width = 0      # widest stacked dispatch

    def note(self, width: int) -> None:
        with self._lock:
            self.archives += width
            if width > 1:
                self.batched += 1
                self.max_width = max(self.max_width, width)
            else:
                self.single += 1

    @property
    def dispatches(self) -> int:
        """Total decode dispatches (stacked + per-archive)."""
        return self.batched + self.single

    def as_dict(self) -> dict:
        return {"batched": self.batched, "single": self.single,
                "dispatches": self.dispatches, "archives": self.archives,
                "max_width": self.max_width}

    def __repr__(self) -> str:
        return (f"DecodeStats(batched={self.batched}, single={self.single}, "
                f"archives={self.archives}, max_width={self.max_width})")


# Peak device bytes one field of a stacked (de)compress holds, per point.
# The interp predictor runs its phases eagerly in float64 with the
# reconstruction, the targets, shifted neighbour copies and the functional
# ``.at[].set`` update all live: on a v5e chip (16 GiB HBM) two stacked
# 513³ fields ran out of device memory with more than 7 f64 words per point
# in use, so 12 words is budgeted.
STACK_BYTES_PER_POINT = 96


@functools.lru_cache(maxsize=1)
def _device_bytes_limit() -> int | None:
    """The first device's memory limit, asked once per process (None where
    the backend reports none, as the CPU does)."""
    import jax
    stats = jax.devices()[0].memory_stats()
    limit = (stats or {}).get("bytes_limit")
    return int(limit) if limit else None


def stack_limit(points: int) -> int | None:
    """How many same-shape fields of ``points`` points one stacked call may
    carry on the default device, or ``None`` where the backend reports no
    memory limit: at least 1, so a field too big to stack still runs
    alone."""
    limit = _device_bytes_limit()
    if limit is None:
        return None
    return max(1, limit // (int(points) * STACK_BYTES_PER_POINT))


def stack_chunks(members: list, points: int) -> list[list]:
    """``members`` split into runs that fit :func:`stack_limit`."""
    cap = stack_limit(points) or len(members) or 1
    return [members[i:i + cap] for i in range(0, len(members), cap)]


def decompress_many(arcs, *, batch: bool = True,
                    stats: DecodeStats | None = None) -> dict:
    """Decode a set of conventional archives, batching where possible.

    ``arcs`` maps name -> archive dict.  Archives whose entry declares
    ``decompress_batched`` and that agree on the entry's ``decode_key``
    run as one stacked eager dispatch (in runs that fit the device's
    memory, :func:`stack_chunks`); everything else decodes per-archive.
    Outputs are bit-identical to per-archive :func:`decompress` either way
    (the decode-side mirror of the conv stage's encode contract), so every
    caller — batched-engine decode, streaming ``iter_decompress``, the
    ``Archive`` handle's random access, the serving tier — may use this
    unconditionally.  ``stats`` (a :class:`DecodeStats`) receives one
    ``note(width)`` per dispatch actually issued.
    """
    out: dict = {}
    groups: dict[tuple, list] = {}
    for name, arc in arcs.items():
        entry = for_archive(arc)
        if batch and entry.decode_batch_supports(arc):
            k = (entry.name, entry.decode_key(arc))
        else:
            k = (entry.name, ("__single__", name))
        groups.setdefault(k, []).append((name, arc, entry))
    for group in groups.values():
        entry = group[0][2]
        points = int(np.prod(group[0][1].get("shape", ())))
        for members in stack_chunks(group, points):
            if len(members) > 1:    # only decode_key-matched archives group
                recs = entry.decompress_batched(
                    [arc for _, arc, _ in members])
                for (name, _, _), rec in zip(members, recs):
                    out[name] = rec
                if stats is not None:
                    stats.note(len(members))
            else:
                for name, arc, e in members:
                    out[name] = e.decompress(arc)
                    if stats is not None:
                        stats.note(1)
    return {name: out[name] for name in arcs}


def _register_builtins() -> None:
    """Built-in compressors; imported lazily so this module stays cheap to
    import from documentation/tooling contexts."""
    from . import szlike, zfplike

    def _lorenzo_compress(x, rel_eb=None, *, abs_eb=None, **kw):
        cfg = kw.pop("config", szlike.SZLikeConfig(predictor="lorenzo"))
        return szlike.compress(x, rel_eb, abs_eb=abs_eb, config=cfg, **kw)

    def _lorenzo_batched(xs, rel_eb=None, *, abs_eb=None, **kw):
        cfg = kw.pop("config", szlike.SZLikeConfig(predictor="lorenzo"))
        return szlike.compress_batched(xs, rel_eb, abs_eb=abs_eb, config=cfg,
                                       **kw)

    register(CompressorEntry(
        name="szlike", kind="szlike",
        compress=szlike.compress, decompress=szlike.decompress,
        archive_nbytes=szlike.archive_nbytes,
        compress_batched=szlike.compress_batched,
        decompress_batched=szlike.decompress_batched,
        decode_key=szlike.decode_key,
        description="SZ3-style multilevel cubic-interpolation predictor"))
    register(CompressorEntry(
        name="szlike-lorenzo", kind="szlike",
        compress=_lorenzo_compress, decompress=szlike.decompress,
        archive_nbytes=szlike.archive_nbytes,
        compress_batched=_lorenzo_batched,
        decompress_batched=szlike.decompress_batched,
        decode_key=szlike.decode_key,
        description="cuSZ-style dual-quantization Lorenzo predictor"))
    register(CompressorEntry(
        name="zfplike", kind="zfplike",
        compress=zfplike.compress, decompress=zfplike.decompress,
        archive_nbytes=zfplike.archive_nbytes,
        compress_batched=zfplike.compress_batched,
        decompress_batched=zfplike.decompress_batched,
        decode_key=zfplike.decode_key,
        description="ZFP-style block-transform with exact correction pass"))
