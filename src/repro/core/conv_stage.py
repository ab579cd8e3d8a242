"""Snapshot-batched conventional-compression stage shared by every engine.

The three engines used to carry their own per-field loop around
``compressors.compress`` (serial: upfront over the snapshot; batched: lazily
per training group; streaming: per field on the reader side).  This module is
the one conventional stage they all call now: it plans the fields it is
handed into groups of identical ``(shape, dtype, error-bound spec)`` and
runs each group through the compressor's *batched* entry point when its
registry entry declares the capability
(:class:`repro.compressors.registry.CompressorEntry.compress_batched`).
With the run's historical single scalar bound every field shares one spec
and the plan degenerates to the original ``(shape, dtype)`` grouping; with
per-field :class:`repro.core.bounds.ErrorBound` specs, fields that share a
spec still batch and fields with distinct bounds split into their own
groups (a fused dispatch hands ``compress_batched`` exactly one spec).

The batched entries execute the group as ONE stacked op sequence (a single
device-op stream for the whole group instead of one per field) and are
contractually **byte-identical** to the per-field path, so archives stay
bit-compatible across engines no matter which path compressed a given field.
Compressors whose entry does not declare batchability — or whose capability
metadata excludes the group's dtype — fall back per-field.

:class:`ConvStats` counts how the work was actually dispatched (groups,
fused calls, per-field fallbacks); engines surface it under
``timing["conv_stage"]`` and the bench smoke profile fails if a multi-field
snapshot regresses to per-field call counts.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Mapping

import numpy as np

from ..compressors import registry
from ..obs import telemetry as obs


def _declared_kwargs(fn, **kw) -> dict:
    """The part of ``kw`` that ``fn`` declares (all of it when ``fn`` takes
    ``**kwargs``): registry entries may wrap third-party compressors that
    know nothing about kernel dispatch or telemetry."""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return {}
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return kw
    return {k: v for k, v in kw.items() if k in params}


@dataclasses.dataclass
class ConvStats:
    """How the conventional stage dispatched its work.

    ``calls`` is the structural dispatch count: one per fused group call
    plus one per per-field fallback — the number the smoke-profile
    regression guard compares against ``fields``.
    """

    fields: int = 0
    groups: int = 0
    batched_fields: int = 0
    fallback_fields: int = 0
    calls: int = 0
    conv_s: float = 0.0
    # Dispatch calls that carried the kernel-lowering request through to the
    # compressor entry (0 for third-party entries without a lowering kwarg).
    lowered_calls: int = 0
    lowering: str = "auto"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def plan_groups(metas: Mapping[str, tuple],
                keys: Mapping[str, tuple] | None = None) -> list[list[str]]:
    """Group field names by ``(shape, dtype[, key])``, preserving input order.

    ``metas`` maps name -> ``(shape, dtype)``.  Fields of one group can run
    through a batched compressor entry as a stacked array.  ``keys``
    optionally refines the plan with a per-field hashable (the error-bound
    spec): fields only share a group when their keys agree too.
    """
    groups: dict[tuple, list[str]] = {}
    for name, (shape, dtype) in metas.items():
        k = (tuple(shape), str(np.dtype(dtype)),
             keys[name] if keys is not None else None)
        groups.setdefault(k, []).append(name)
    return list(groups.values())


class ConvStage:
    """Plan/executor for the conventional stage of one compression run.

    Holds the compressor registry entry and the run's error-bound spec;
    every engine funnels its fields through :meth:`run` (all at once, per
    training group, or per transient aux load) and reads the accumulated
    :class:`ConvStats` afterwards.
    """

    def __init__(self, compressor: str, rel_eb: float | None = None,
                 abs_eb: float | None = None, *, batch: bool = True,
                 bounds: Mapping | None = None, telemetry=None,
                 lowering: str = "auto"):
        self.entry = registry.get(compressor)   # unknown name -> ValueError
        self.rel_eb = rel_eb
        self.abs_eb = abs_eb
        self.batch = batch
        # Per-field ErrorBound specs; fields absent here use the run scalars.
        self.bounds = dict(bounds) if bounds else None
        self.lowering = lowering
        self.stats = ConvStats(lowering=lowering)
        self.tel = telemetry if telemetry is not None else obs.NULL
        # The lowering request and the telemetry handle ride along only
        # when the entry declares the kwarg — third-party compressor entries
        # are untouched.
        self._kw = _declared_kwargs(self.entry.compress, lowering=lowering,
                                    telemetry=self.tel)
        self._kw_batched = (
            _declared_kwargs(self.entry.compress_batched, lowering=lowering,
                             telemetry=self.tel)
            if self.entry.compress_batched is not None else {})

    def bound_for(self, name: str) -> tuple[float | None, float | None]:
        """``(rel_eb, abs_eb)`` this run will hand the compressor for one
        field (abs takes precedence inside the compressor entry points).
        Doubles as the plan's grouping key — the spec's ``conv_key``."""
        if self.bounds is not None and name in self.bounds:
            return self.bounds[name].conv_key()
        return (self.rel_eb, self.abs_eb)

    def plan(self, metas: Mapping[str, tuple]) -> list[list[str]]:
        keys = ({n: self.bound_for(n) for n in metas}
                if self.bounds is not None else None)
        return plan_groups(metas, keys=keys)

    def run(self, fields: Mapping[str, np.ndarray], *,
            batch: bool | None = None
            ) -> dict[str, tuple[dict, np.ndarray]]:
        """Compress ``fields``; returns ``{name: (archive, reconstruction)}``.

        Same-``(shape, dtype)`` groups go through the fused batched entry
        when the registry capability allows it, in runs that fit the
        device's memory (:func:`repro.compressors.registry.stack_chunks`);
        everything else runs per-field.  Output payloads are byte-identical either way.
        ``batch`` overrides the stage default for this call (the streaming
        scheduler turns it off when the fused path's working set would not
        fit its residency budget).
        """
        batch = self.batch if batch is None else batch
        t0 = time.time()
        out: dict[str, tuple[dict, np.ndarray]] = {}
        arrs = {n: np.asarray(x) for n, x in fields.items()}
        metas = {n: (a.shape, a.dtype) for n, a in arrs.items()}
        tel = self.tel
        with tel.span("conv", fields=len(arrs)) as sp:
            calls0 = self.stats.calls
            for group in self.plan(metas):
                self.stats.groups += 1
                tel.counter("conv.groups").add()
                dtype = metas[group[0]][1]
                rel, ab = self.bound_for(group[0])  # one spec/group, by plan
                stackable = (batch and len(group) > 1
                             and self.entry.batch_supports(dtype))
                chunks = (registry.stack_chunks(
                    group, int(np.prod(metas[group[0]][0])))
                    if stackable else [[n] for n in group])
                for chunk in chunks:
                    if len(chunk) > 1:
                        results = self.entry.compress_batched(
                            [arrs[n] for n in chunk], rel, abs_eb=ab,
                            **self._kw_batched)
                        self.stats.calls += 1
                        self.stats.batched_fields += len(chunk)
                        self.stats.lowered_calls += \
                            "lowering" in self._kw_batched
                        tel.counter("conv.dispatches").add()
                        tel.counter("conv.batched_fields").add(len(chunk))
                        out.update(zip(chunk, results))
                        continue
                    for n in chunk:
                        out[n] = self.entry.compress(arrs[n], rel, abs_eb=ab,
                                                     **self._kw)
                        self.stats.calls += 1
                        self.stats.fallback_fields += 1
                        self.stats.lowered_calls += "lowering" in self._kw
                        tel.counter("conv.dispatches").add()
                        tel.counter("conv.fallback_fields").add()
            sp.set(calls=self.stats.calls - calls0)
        self.stats.fields += len(arrs)
        self.stats.conv_s += time.time() - t0
        return out
