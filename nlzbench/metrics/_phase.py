"""Device-idle time inside named program spans: what one host phase of the
program costs the device."""
from __future__ import annotations

from nlzbench import trace_reduce


def idle_in_spans_pct(run, names) -> float | None:
    """Device-idle time of the traced window inside the union of the
    program spans called any of ``names``, as a share of the window.
    ``None`` when no such span reaches into the window (a program that
    does not emit them)."""
    v = run.trace
    if v is None or v.window_s <= 0 or not v.dev.devices:
        return None
    inside = trace_reduce.clip(trace_reduce.merge(
        (s, e) for n, s, e in v.spans if n in names), v.lo, v.hi)
    if not inside:
        return None
    idle = trace_reduce.intersect(v.dev.idle_gaps(v.lo, v.hi), inside)
    return 100.0 * trace_reduce.length(idle) * 1e-9 / v.window_s
