"""The chip: device check, table of peaks, compile clock.

Peaks are the published figures of Google Cloud's "TPU v5e" page, keyed by
``device_kind`` as JAX reports it.  A kind that is not in the table is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # bf16 MXU peak, HBM bandwidth and capacity of one chip.
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class DeviceError(RuntimeError):
    """No usable accelerator for this cell."""


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def check(chips: int, platform: str = "tpu") -> dict:
    """The device record of the result line; raises :class:`DeviceError`
    when JAX sees another platform or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != platform:
        raise DeviceError(f"platform {info['platform']!r}, want {platform!r}")
    if info["count"] < chips:
        raise DeviceError(f"{info['count']} devices, cell wants {chips}")
    return info


def memory_peak_bytes(count: int) -> int | None:
    """Peak bytes in use on the fullest of the first ``count`` devices,
    where the backend reports it."""
    import jax
    peak = None
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


class CompileClock:
    """XLA backend compiles and persistent-cache hits, from
    ``jax.monitoring``.  ``mark()`` snapshots the counts so a window can
    report how many compiles fell inside it."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits
