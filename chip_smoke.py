#!/usr/bin/env python3
"""Bring-up smoke run of NeurLZ on a TPU, through the user entry points.

Default (one chip): the Nyx-shaped snapshot at the paper's 512³ runtime
block goes ``repro.NeurLZ(...).compress`` → ``Archive.save`` →
``Archive.open`` → ``decode`` → ``ArchiveServer``, in one process:

    python3 chip_smoke.py [--shape 512,512,512] [--fields N] [--epochs E]

Phases, each of which fails the run (nonzero exit, no result line):

* device  — ``jax.devices()[0].platform`` must be ``tpu``; never falls
  back to the CPU;
* kernels — every lowering variant's byte-parity probe; a probe that
  *raised* (a kernel the compiler refused) fails, a byte mismatch is an
  allowed fallback and is printed;
* compress — batched engine, ``lowering="auto"``, strict rel bound 1e-3,
  cross-field channels on, archive written to a file; no field may be
  degraded to conv-only.  Groups train under ``field_batching="unroll"``
  (the exact per-field graph): ``auto`` would first compile the stacked
  gradient twice more for its parity probe, minutes of compile at 512²
  planes that the run's time limit does not have;
* decode  — every field read back from the file within its bound;
* serve   — concurrent ``ArchiveServer`` requests and a ROI read, each
  bit-identical to ``Archive.decode``.

``--chips 4`` runs only the field-sharded path: the same snapshot under
``field_shard=True`` across four chips against ``field_shard=False,
prefetch=False`` on one, which must give byte-identical archives with
training spread over more than one device.

The last line of standard output is the result,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  The persistent compilation cache
goes where :mod:`repro.compile_cache` says.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DATASET = "nyx"
REL_EB = 1e-3


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the field-sharded four-chip phase")
    ap.add_argument("--shape", default="512,512,512",
                    help="field block (comma ints); the paper's runtime "
                         "block is 512³")
    ap.add_argument("--fields", type=int, default=None,
                    help="Nyx fields compressed, in snapshot order (≥ 2 "
                         "so cross-field channels have a partner).  "
                         "Default 2 on one chip, which keeps the 512³ run "
                         "inside its time limit; 4 with --chips 4, two "
                         "training groups to spread")
    ap.add_argument("--epochs", type=int, default=2)
    args = ap.parse_args(argv)
    args.shape = tuple(int(s) for s in args.shape.split(","))
    if args.fields is None:
        args.fields = 4 if args.chips == 4 else 2
    if args.fields < 2:
        ap.error("--fields must be at least 2 (cross-field learning)")
    return args


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums XLA backend compile time and persistent-cache hits reported by
    ``jax.monitoring`` (the first vs a second run shows the cache)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def phase_device(platform: str = "tpu", count: int = 1) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log("device", f"{info['platform']} {info['kind']} x{info['count']}")
    if info["platform"] != platform:
        raise SmokeFailure(f"platform {info['platform']!r}, want "
                           f"{platform!r}: refusing to run elsewhere")
    if info["count"] < count:
        raise SmokeFailure(f"{info['count']} devices, want {count}")
    return info


def phase_kernels() -> dict:
    """Every variant's parity verdict; fails on a probe that raised."""
    from repro.kernels import dispatch
    dispatch._register_all()
    report = dispatch.parity_report()
    raised = []
    for op, rows in report.items():
        for low, verdict in rows.items():
            log("kernels", f"{op:14s} {low:7s} {verdict}")
            if dispatch.RAISED in verdict:
                raised.append(f"{op}/{low}")
    if raised:
        raise SmokeFailure(f"parity probes raised: {', '.join(raised)}")
    return report


def snapshot(shape, num_fields: int):
    """The first ``num_fields`` Nyx fields and their cross-field map (each
    field's partner is among the first two, so every field keeps one)."""
    from repro.data import fields as F
    names = F.DATASET_FIELDS[DATASET][:num_fields]
    flds = F.make_fields(DATASET, shape=shape, seed=0, names=names)
    return flds, {n: F.DEFAULT_CROSS_FIELD[DATASET][n] for n in names}


def session(cross, epochs: int, **engine_kw):
    import repro
    return repro.NeurLZ(
        model=repro.ModelConfig(epochs=epochs, cross_field=cross),
        engine=repro.EngineConfig(engine="batched", lowering="auto",
                                  field_batching="unroll", **engine_kw),
        regulation=repro.RegulationConfig(mode="strict"))


def _degraded(arc) -> list:
    bad = set(arc["timing"].get("degraded_fields", []))
    bad |= {n for n, e in arc["fields"].items() if e.get("degraded")}
    return sorted(bad)


def phase_compress(flds, cross, epochs: int, path: str) -> None:
    from repro.kernels import dispatch
    sess = session(cross, epochs)
    t0 = time.perf_counter()
    arc = sess.compress(flds, rel_eb=REL_EB)
    t_comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    nbytes = arc.save(path)
    t_save = time.perf_counter() - t0
    tm = arc["timing"]
    cs = tm["conv_stage"]
    log("compress", f"{len(flds)} fields {next(iter(flds.values())).shape} "
        f"cross={dict(cross)} epochs={epochs}: {t_comp:.2f}s "
        f"(conv {tm['conv_s']:.2f}s in {cs['calls']} calls, "
        f"{cs['batched_fields']} fields stacked; train+finalize "
        f"{tm['train_s']:.2f}s), save {t_save:.2f}s, {nbytes} B")
    for (op, req, chosen), n in sorted(dispatch.resolution_counts().items()):
        log("compress", f"resolved {op}: {req} -> {chosen} x{n}")
    bad = _degraded(arc)
    if bad:
        raise SmokeFailure(f"degraded fields: {bad}")


def phase_decode(flds, path: str) -> dict:
    """Every field through ``Archive.decode``; returns the decoded arrays."""
    import numpy as np
    import repro
    from repro.core import metrics
    t0 = time.perf_counter()
    worst, decoded = {}, {}
    with repro.Archive.open(path) as arc:
        br = arc.bitrate()
        for name, x in flds.items():
            y = decoded[name] = arc.decode(name)
            eb = arc.entry(name)["abs_eb"]
            r = float(np.abs(y.astype(np.float64)
                             - x.astype(np.float64)).max()) / eb
            worst[name] = r
            log("decode", f"{name:20s} max|err|/eb {r:.6f}  "
                f"bitrate {br[name]['bitrate']:.4f} b/val  "
                f"PSNR {metrics.psnr(x, y):.2f} dB")
    t = time.perf_counter() - t0
    log("decode", f"{len(flds)} fields in {t:.2f}s")
    over = {n: r for n, r in worst.items() if not r <= 1.0}
    if over:
        raise SmokeFailure(f"error bound exceeded: {over}")
    return decoded


def phase_serve(path: str, ref: dict) -> None:
    """``ref``: every field as ``Archive.decode`` returned it."""
    from repro.serve import ArchiveServer
    t0 = time.perf_counter()
    names = list(ref)
    wanted = [names[i % len(names)] for i in range(4)]
    with ArchiveServer(path) as srv:
        # Concurrent clients: every submit is in flight before any result.
        with ThreadPoolExecutor(len(wanted)) as pool:
            futs = list(pool.map(srv.submit, wanted))
        got = [f.result(600) for f in futs]
        roi = (slice(0, max(1, ref[names[0]].shape[0] // 2)),)
        slab = srv.decode(names[0], roi=roi, timeout=600)
        st = srv.stats()
    bad = [n for n, y in zip(wanted, got)
           if y.tobytes() != ref[n].tobytes()]
    if slab.tobytes() != ref[names[0]][roi].tobytes():
        bad.append(f"{names[0]}[roi]")
    t = time.perf_counter() - t0
    log("serve", f"{len(wanted)} concurrent requests + 1 ROI read in "
        f"{t:.2f}s, {st['decode']['dispatches']} decode dispatches, "
        f"bit-identical to Archive.decode: {not bad}")
    if bad:
        raise SmokeFailure(f"served bytes differ from Archive.decode: {bad}")


def _train_placement(tel) -> dict:
    return {s.attrs["group"]: s.attrs.get("devices", [])
            for s in tel.spans if s.name == "train" and "group" in s.attrs}


def phase_four_chips(flds, cross, epochs: int) -> None:
    """field_shard=True on every device vs field_shard=False,
    prefetch=False (all on device 0): byte-identical field entries, and
    training on more than one device."""
    import jax
    import repro
    from repro.core import archive as arc_io
    runs = {}
    for label, kw in (("sharded", {"field_shard": True}),
                      ("one-device", {"field_shard": False,
                                      "prefetch": False})):
        tel = repro.Telemetry()
        t0 = time.perf_counter()
        arc = session(cross, epochs, telemetry=tel, **kw).compress(
            flds, rel_eb=REL_EB)
        t = time.perf_counter() - t0
        place = _train_placement(tel)
        runs[label] = (arc_io.dumps(arc["fields"]), place)
        log("chips4", f"{label}: {t:.2f}s on {len(jax.devices())} devices, "
            f"training placement {place}")
        bad = _degraded(arc)
        if bad:
            raise SmokeFailure(f"{label}: degraded fields: {bad}")
    used = {d for devs in runs["sharded"][1].values() for d in devs}
    same = runs["sharded"][0] == runs["one-device"][0]
    log("chips4", f"sharded training used devices {sorted(used)}; archives "
        f"byte-identical: {same}")
    if len(used) < 2:
        raise SmokeFailure(f"sharded run trained on devices {sorted(used)} "
                           "only")
    if not same:
        raise SmokeFailure("sharded and one-device archives differ")


# ---------------------------------------------------------------------------

def run(args, *, platform: str = "tpu") -> dict:
    """Every phase of one invocation; returns the device record or raises
    :class:`SmokeFailure`."""
    clock = CompileClock()
    t_start = time.perf_counter()
    dev = phase_device(platform, count=args.chips)
    t0 = time.perf_counter()
    flds, cross = snapshot(args.shape, args.fields)
    log("data", f"{DATASET} {args.shape} x{len(flds)} fields "
        f"{sum(v.nbytes for v in flds.values())} B in "
        f"{time.perf_counter() - t0:.2f}s")
    if args.chips == 4:
        phase_four_chips(flds, cross, args.epochs)
    else:
        phase_kernels()
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "snapshot.nlz")
            phase_compress(flds, cross, args.epochs, path)
            phase_serve(path, phase_decode(flds, path))
    log("total", f"{time.perf_counter() - t_start:.2f}s, XLA compile "
        f"{clock.compile_s:.2f}s, persistent-cache hits {clock.cache_hits}")
    return dev


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    log("cache", f"persistent compilation cache: {compile_cache.setup()}")
    try:
        dev = run(args)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
