"""Fault tolerance for the compression stack: injection, retry, degradation.

Three cooperating pieces, wired through ``NeurLZConfig.faults`` /
``NeurLZ(faults=...)`` the same way telemetry rides on
``config.telemetry``:

* :class:`FaultInjector` — deterministic site/invocation fault registry
  (``"writer.add_entry"``, ``"train.<field>"``, ``"decode.entry"``,
  ``"reader.load"``).  Tests and chaos runs schedule exact failures;
  production leaves it ``None`` and every check is a shared no-op.
* :class:`RetryPolicy` / :func:`retry_with_backoff` — bounded exponential
  backoff around transient I/O sites (archive writer, streaming reader
  thread, ``Archive.decode``), counted on telemetry as ``faults.retries``.
* **Graceful degradation** — a per-field enhancer failure (non-finite
  loss, injected fault, OOM) downgrades that field to a conv-only entry
  that still honors its exact error bound (the conventional stage alone
  guarantees ``|x - x'| <= eb``), recorded in the entry
  (``entry["degraded"]``), counted as ``faults.degraded``, and listed in
  ``timing["degraded_fields"]`` — instead of aborting the snapshot.
  Degradation *reasons* are normalized (:func:`degrade_reason`) so all
  three engines emit byte-identical degraded entries for the same
  failure.

The straggler watchdog reuses the seeded
:class:`repro.checkpoint.fault_tolerance.StepWatchdog`: give
``FaultConfig.straggler_deadline_s`` a value and the streaming scheduler
flags field groups that exceed it via ``faults.stragglers`` telemetry.

Like ``repro.obs`` this package imports neither jax nor the engines, so
building a :class:`FaultConfig` never flips the x64 switch.
"""
from __future__ import annotations

import dataclasses

from ..checkpoint.fault_tolerance import StepWatchdog  # noqa: F401
from .injector import FaultInjector, InjectedFault, NULL_INJECTOR
from .retry import RetryPolicy, retry_with_backoff

__all__ = [
    "FaultConfig", "FaultInjector", "InjectedFault", "RetryPolicy",
    "StepWatchdog", "retry_with_backoff", "of", "DEFAULT",
    "is_degradable", "degrade_reason", "NULL_INJECTOR",
]

# Failures eligible for conv-only degradation.  Deliberately narrow: a
# genuine bug (shape mismatch, TypeError) must still crash loudly — only
# the failure modes a long-running HPC job meets (injected chaos, host or
# device memory exhaustion, float traps) downgrade a field.
DEGRADABLE_EXCEPTIONS = (InjectedFault, MemoryError, FloatingPointError)


# Markers of a *compile-time* RESOURCE_EXHAUSTED: a kernel or program the
# compiler refused ("Ran out of memory in memory space vmem", Mosaic or
# XLA:TPU compile errors).  Those are bugs in what the program asks the
# device to run, identical on every retry and every field, so they abort.
_COMPILE_MARKERS = ("compil", "mosaic", "vmem")


def is_degradable(exc: BaseException) -> bool:
    """True when a per-field enhancer failure should degrade the field to
    conv-only instead of aborting the snapshot."""
    if isinstance(exc, DEGRADABLE_EXCEPTIONS):
        return True
    # Device memory exhausted at run time surfaces as a jax runtime error
    # "RESOURCE_EXHAUSTED: ..." — matched by message so this package never
    # imports jax.  The same status from the compiler is not degradable.
    msg = str(exc)
    if "RESOURCE_EXHAUSTED" not in msg:
        return False
    low = msg.lower()
    return not any(m in low for m in _COMPILE_MARKERS)


def degrade_reason(exc: BaseException | None = None) -> str:
    """Normalized degradation reason recorded in the entry.  The same
    failure must yield the same string in every engine — the cross-engine
    byte-identity contract extends to degraded entries."""
    if exc is None:
        return "non-finite-loss"
    if isinstance(exc, InjectedFault):
        return "injected"
    return f"error:{type(exc).__name__}"


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Fault-tolerance knobs carried by ``NeurLZConfig.faults``.

    ``injector=None`` disables injection (production), ``retry=None``
    disables retries (fail fast — the pre-PR-8 behavior), ``degrade``
    controls conv-only degradation, ``straggler_deadline_s`` arms the
    per-group watchdog on the streaming scheduler.
    """

    injector: FaultInjector | None = None
    retry: RetryPolicy | None = None
    degrade: bool = True
    straggler_deadline_s: float | None = None

    def check(self, site: str) -> None:
        """Injection probe for ``site`` (no-op without an injector)."""
        if self.injector is not None:
            self.injector.check(site)

    def run(self, fn, *, site: str, tel=None):
        """Probe ``site`` then run ``fn`` — under the retry policy when one
        is set, else one straight attempt.  The probe sits *inside* the
        retried closure, so a transiently-planned injection heals on
        retry exactly like a real transient I/O error."""
        from ..obs import telemetry as obs_lib

        def attempt():
            self.check(site)
            return fn()

        if self.retry is None:
            return attempt()
        return retry_with_backoff(attempt, self.retry, site=site,
                                  tel=tel if tel is not None else obs_lib.NULL)


#: Shared default: no injection, no retries, degradation on.
DEFAULT = FaultConfig()


def of(config) -> FaultConfig:
    """The :class:`FaultConfig` carried by a config-like object
    (``.faults`` attribute), or :data:`DEFAULT`."""
    fc = getattr(config, "faults", None)
    return fc if fc is not None else DEFAULT
