"""Lowering dispatch for the three hot computations.

Every engine funnels its hot loops through one entry point per op —
``dnn_forward`` (the skipping-DNN conv chain), ``fused_enhance``
(enhance + regulate + outlier capture) and ``lorenzo`` (Lorenzo
predict/quantize) — selected by ``NeurLZConfig.lowering``:

* ``eager``  — the historical op-by-op path; the byte-level reference.
* ``jit``    — jit-compiled variants with *explicit bit-stable arithmetic*:
  contractions pinned via ``jax.lax`` ops at ``precision=HIGHEST`` and
  FMA-contraction suppressed (``jax.lax.optimization_barrier`` between the
  multiply and the add at every fused-multiply-add site), so the compiled
  path produces byte-identical archives.
* ``pallas`` — the hand-written TPU kernels in this package (``lorenzo``
  and ``fused_enhance``; ``dnn_forward`` has none, since it runs inside
  the training step's gradient).
* ``auto``   — pallas where supported, else jit, else eager.

The contract is *verified, not assumed*: before a non-eager variant is used
it must pass its **parity probe** — a byte-for-byte comparison against the
eager reference on canary inputs (including adversarial rounding-boundary
values).  A variant that cannot honor the contract on this
(backend, dtype, shape-class) falls back to eager, and the fallback is
recorded (:func:`fallbacks`) so tests and telemetry can see it — a probe
that returned a mismatch apart from one that raised.  Probe verdicts are
cached per (op, lowering, backend, probe-key).

Backend identification is a process-wide cached probe
(:func:`backend`) instead of a per-call ``jax.default_backend()`` sniff;
tests force it with :func:`force_backend`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax

LOWERINGS = ("eager", "jit", "pallas", "auto")

# Preference order `auto` walks (first supported + probe-passing wins).
_AUTO_ORDER = ("pallas", "jit")

_lock = threading.Lock()
_forced_backend: str | None = None


@functools.lru_cache(maxsize=1)
def _default_backend() -> str:
    return jax.default_backend()


def backend() -> str:
    """The cached JAX backend name ('cpu' | 'gpu' | 'tpu').

    Cached once per process (the backend cannot change under JAX), unless a
    test is inside :func:`force_backend`.
    """
    return _forced_backend if _forced_backend is not None else _default_backend()


@contextlib.contextmanager
def force_backend(name: str):
    """Pretend the process runs on ``name`` for the duration of the block.

    Test hook: lets the parity-probe / fallback machinery be exercised for
    backends the box does not have.  Probe verdicts cached under the forced
    backend are dropped on exit so they cannot leak into real resolution.
    """
    global _forced_backend
    with _lock:
        prev, _forced_backend = _forced_backend, name
    try:
        yield
    finally:
        with _lock:
            _forced_backend = prev
            stale = [k for k in _verdicts if k[2] == name]
            for k in stale:
                del _verdicts[k]


@dataclasses.dataclass
class _Variant:
    fn: object
    probe: object | None = None      # () -> bool: byte-parity vs eager
    backends: tuple | None = None    # None = any backend


# op name -> {lowering: _Variant}
_ops: dict[str, dict[str, _Variant]] = {}
# (op, lowering, backend, key) -> None (probe passed) | failure reason
_verdicts: dict[tuple, str | None] = {}
# (op, requested, chosen) -> count
_resolutions: dict[tuple, int] = {}
# [(op, lowering, backend, reason)] for every fallback decision
_fallbacks: list[tuple] = []


def register(op: str, lowering: str, fn, *, probe=None, backends=None) -> None:
    """Register a lowering variant for ``op``.

    ``probe`` is a zero-arg callable returning True iff the variant is
    byte-identical to the eager reference on this backend's canary inputs
    (it should *try to break* the variant — rounding-boundary values, odd
    shapes).  ``backends`` restricts the variant to those backend names.
    Registration happens at import time in the module that owns the
    implementation (skipping_dnn / regulation / szlike), so there are no
    import cycles through this module.
    """
    if lowering not in ("eager", "jit", "pallas"):
        raise ValueError(f"unknown lowering {lowering!r}")
    with _lock:
        _ops.setdefault(op, {})[lowering] = _Variant(
            fn=fn, probe=probe,
            backends=tuple(backends) if backends is not None else None)


MISMATCH = "parity probe mismatched"
RAISED = "parity probe raised"


def _probe_ok(op: str, lowering: str, var: _Variant, key=()) -> bool:
    """Run (once per verdict key) and cache ``var``'s parity probe.

    A probe that *returns* False is a byte mismatch — the honest fallback
    the bit-stability contract allows.  A probe that *raises* means the
    variant could not even run here (a kernel the compiler refused, a
    missing lowering rule); it also falls back, but is recorded apart
    (:data:`RAISED` plus the exception's type and first line) so a refused
    kernel is never mistaken for a mismatch.
    """
    if var.probe is None:
        return True
    vkey = (op, lowering, backend(), key)
    with _lock:
        if vkey in _verdicts:
            return _verdicts[vkey] is None
    try:
        reason = None if var.probe() else MISMATCH
    except Exception as exc:   # a variant that cannot even run cannot be bit-stable
        first = (str(exc).strip().splitlines() or [""])[0]
        reason = f"{RAISED} {type(exc).__name__}: {first}"
    with _lock:
        _verdicts[vkey] = reason
    if reason is not None:
        _note_fallback(op, lowering, reason)
    return reason is None


def _verdict(op: str, lowering: str, key=()) -> str | None:
    """The cached probe failure reason (None = passed or not probed)."""
    with _lock:
        return _verdicts.get((op, lowering, backend(), key))


def resolve(op: str, lowering: str = "auto", *, key=()):
    """Pick the implementation for ``op`` under ``lowering``.

    Returns ``(fn, chosen)`` where ``chosen`` names the lowering actually
    selected — ``"eager"`` whenever the requested one is unregistered,
    unsupported on this backend, or fails its parity probe.  ``key`` feeds
    the probe-verdict cache (callers pass a dtype/shape-class when parity
    depends on it).
    """
    if lowering not in LOWERINGS:
        raise ValueError(f"unknown lowering {lowering!r} (want one of "
                         f"{LOWERINGS})")
    variants = _ops.get(op)
    if not variants or "eager" not in variants:
        raise KeyError(f"op {op!r} has no registered eager reference")
    candidates = _AUTO_ORDER if lowering == "auto" else (lowering,)
    for cand in candidates:
        if cand == "eager":
            break
        var = variants.get(cand)
        if var is None:
            if lowering != "auto":
                _note_fallback(op, cand, "unregistered")
            continue
        if var.backends is not None and backend() not in var.backends:
            if lowering != "auto":
                _note_fallback(op, cand, f"backend {backend()!r} unsupported")
            continue
        if not _probe_ok(op, cand, var, key):
            continue
        _count(op, lowering, cand)
        return var.fn, cand
    _count(op, lowering, "eager")
    return variants["eager"].fn, "eager"


def _note_fallback(op, lowering, reason) -> None:
    with _lock:
        _fallbacks.append((op, lowering, backend(), reason))


def _count(op, requested, chosen) -> None:
    with _lock:
        k = (op, requested, chosen)
        _resolutions[k] = _resolutions.get(k, 0) + 1


def fallbacks() -> list[tuple]:
    """Every recorded ``(op, lowering, backend, reason)`` fallback."""
    with _lock:
        return list(_fallbacks)


def resolution_counts() -> dict[tuple, int]:
    """``(op, requested, chosen) -> count`` since process start."""
    with _lock:
        return dict(_resolutions)


def clear_cache() -> None:
    """Drop probe verdicts + fallback/resolution records (test isolation)."""
    with _lock:
        _verdicts.clear()
        _fallbacks.clear()
        _resolutions.clear()


def parity_report() -> dict:
    """Probe every registered non-eager variant on this backend.

    ``{op: {lowering: "ok" | "unregistered" | "fallback (<reason>)"}}``
    — the local parity check the README documents
    (`python -m repro.kernels.dispatch`).  A probe that raised reads
    ``fallback (parity probe raised <Type>: <first line>)``; a byte
    mismatch reads ``fallback (parity probe mismatched)``.
    """
    report: dict = {}
    for op, variants in sorted(_ops.items()):
        report[op] = {}
        for low in ("jit", "pallas"):
            var = variants.get(low)
            if var is None:
                report[op][low] = "unregistered"
            elif var.backends is not None and backend() not in var.backends:
                report[op][low] = (f"fallback (backend {backend()!r} "
                                   "unsupported)")
            elif _probe_ok(op, low, var):
                report[op][low] = "ok"
            else:
                report[op][low] = f"fallback ({_verdict(op, low)})"
    return report


def _register_all() -> None:
    """Import every module that registers variants (CLI/report helper)."""
    from ..compressors import szlike            # noqa: F401
    from ..core import regulation, skipping_dnn  # noqa: F401


if __name__ == "__main__":
    # Under ``python -m`` this file executes as ``__main__``, a *different*
    # module object from ``repro.kernels.dispatch`` — the one the op owners
    # register into.  Report through the canonical module, not this copy.
    from repro.kernels import dispatch as _dispatch

    _dispatch._register_all()
    print(f"backend: {_dispatch.backend()}")
    for op, rows in _dispatch.parity_report().items():
        for low, verdict in rows.items():
            print(f"{op:16s} {low:8s} {verdict}")
