"""Reduce a JAX profiler trace to device busy time, per-program device
time and host-span attribution.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are ``/device:<KIND>:<n>``;
on each, the ``XLA Ops`` line gives busy intervals and the ``XLA Modules``
line gives one event per program execution, named after the jitted
function (``jit_<name>``, sometimes with a ``(<id>)`` suffix).  Times are in
nanoseconds of the profiler's clock; :class:`ClockMap` carries host
``time.perf_counter`` readings onto it through annotations whose host
times the caller recorded.
"""
from __future__ import annotations

import glob
import os
import re
import statistics

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = tuple[float, float]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def merge(intervals) -> list[Interval]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals, lo: float, hi: float) -> list[Interval]:
    return intersect(intervals, [(lo, hi)])


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


class DeviceTrace:
    """Per-device op and program intervals of one trace."""

    def __init__(self, profile):
        self.ops: dict[str, list[Interval]] = {}        # device -> busy
        self.op_events: dict[str, list[tuple[str, float, float]]] = {}
        self.modules: dict[str, list[tuple[str, float, float]]] = {}
        self.annotations: dict[str, list[Interval]] = {}
        for plane in profile.planes:
            if _DEVICE_PLANE.match(plane.name):
                self._read_device(plane)
            else:
                self._read_host(plane)

    @classmethod
    def from_file(cls, path: str) -> "DeviceTrace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(path))

    def _read_device(self, plane) -> None:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif line.name == "XLA Modules":
                mods = [(module_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        if not ops and not mods:
            return
        self.op_events[plane.name] = ops or mods
        self.ops[plane.name] = merge((s, e) for _, s, e in (ops or mods))
        self.modules[plane.name] = mods

    def _read_host(self, plane) -> None:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("nlzbench."):
                    self.annotations.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def busy(self, lo: float, hi: float) -> dict[str, list[Interval]]:
        """Each device's busy intervals inside ``[lo, hi]``."""
        return {d: clip(iv, lo, hi) for d, iv in self.ops.items()}

    def busy_ns(self, lo: float, hi: float) -> float:
        """Busy nanoseconds inside ``[lo, hi]``, averaged over devices."""
        b = self.busy(lo, hi)
        return (sum(length(iv) for iv in b.values()) / len(b)) if b else 0.0

    def module_intervals(self, names, lo: float, hi: float
                         ) -> list[Interval]:
        """Union over devices of the executions of the programs whose name
        contains any of ``names``, inside ``[lo, hi]``."""
        iv = [(s, e) for mods in self.modules.values()
              for n, s, e in mods if any(k in n for k in names)]
        return clip(merge(iv), lo, hi)

    def top_modules(self, lo: float, hi: float, k: int = 10
                    ) -> list[tuple[str, float]]:
        """Programs by device seconds inside ``[lo, hi]`` (top ``k``)."""
        tot: dict[str, float] = {}
        for dev, mods in self.modules.items():
            for n, s, e in mods:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    tot[n] = tot.get(n, 0.0) + d / len(self.modules)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [(n, ns * 1e-9) for n, ns in top]

    def idle_gaps(self, lo: float, hi: float) -> list[Interval]:
        """Gaps inside ``[lo, hi]`` in which no device ran anything."""
        busy = merge(iv for ivs in self.busy(lo, hi).values() for iv in ivs)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps


class ClockMap:
    """Host ``perf_counter`` seconds -> profiler nanoseconds.

    ``pairs`` are ``(annotation start ns, perf_counter seconds read just
    before entering it)``; the offset is their median difference."""

    def __init__(self, pairs):
        pairs = list(pairs)
        if not pairs:
            raise ValueError("no annotation to align the host clock with")
        self.offset_ns = statistics.median(ns - t * 1e9 for ns, t in pairs)

    def ns(self, perf_s: float) -> float:
        return perf_s * 1e9 + self.offset_ns


def attribute(gaps, spans, k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` longest gaps, each named by the innermost host span
    (shortest covering ``(name, start_ns, end_ns)``) at its midpoint."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "outside spans"
        out.append((name, (e - s) * 1e-9))
    return out
