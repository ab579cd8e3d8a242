"""Observability for the NeurLZ engines: spans, counters, learning traces.

Usage::

    import repro
    from repro import obs

    tel = obs.Telemetry()
    sess = repro.NeurLZ(engine="streaming", telemetry=tel)
    sess.compress_to(fields, "snap.nlzs", rel_eb=1e-3)

    tel.export_chrome_trace("trace.json")   # flame graph in Perfetto
    tel.export_jsonl("events.jsonl")        # line-per-event log
    tel.summary()                           # aggregated dict
    tel.trace("temperature")                # per-epoch learning trajectory

Pass no telemetry (the default) and every instrumentation point degrades to
a shared no-op singleton — the disabled path allocates nothing and archives
are byte-identical to an uninstrumented run.

This package imports neither jax nor ``repro.core`` — creating a handle
never flips the x64 switch or pays an engine import.
"""
from .telemetry import (NULL, TIMING_KEYS, Counter, Gauge,  # noqa: F401
                        NullTelemetry, SpanRecord, Telemetry,
                        TelemetryConfig, build_timing, count_h2d,
                        learning_trace, of, to_host)
from .export import (chrome_trace, summary, write_chrome_trace,  # noqa: F401
                     write_jsonl)

__all__ = [
    "Telemetry", "TelemetryConfig", "NullTelemetry", "NULL", "of",
    "to_host", "count_h2d",
    "Counter", "Gauge", "SpanRecord", "TIMING_KEYS",
    "build_timing", "learning_trace",
    "write_jsonl", "chrome_trace", "write_chrome_trace", "summary",
]
