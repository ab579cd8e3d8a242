"""End-to-end NeurLZ driver (the paper's workload): multi-field block,
cross-field learning, per-field error bounds, strict error regulation,
archive on disk, full validation report — on the first-class session API
(``repro.NeurLZ`` / ``repro.Archive``).

    PYTHONPATH=src python examples/compress_field.py [--dataset nyx]
        [--shape 32,48,48] [--eb 1e-3] [--epochs 8] [--mode strict]
        [--field-eb name=1e-2 --field-eb other=abs:0.5:relaxed]
"""
import argparse
import os
import resource
import sys
import tempfile

import numpy as np

import repro
from repro import compile_cache
from repro.compressors import registry
from repro.core import metrics
from repro.data import fields as F


def list_compressors() -> None:
    """Print the compressor registry (names, capabilities, archive kinds)."""
    print(f"{'name':18s} {'kind':10s} {'batchable':9s} {'dec_batch':9s} "
          f"{'dtypes':18s} description")
    for e in registry.entries():
        dts = ",".join(e.dtypes)
        print(f"{e.name:18s} {e.kind:10s} {str(e.batchable):9s} "
              f"{str(e.decode_batchable):9s} {dts:18s} {e.description}")


def parse_field_eb(spec: str) -> tuple[str, repro.ErrorBound]:
    """``name=1e-2`` (relative) | ``name=abs:0.5`` | ``name=1e-3:relaxed``
    | ``name=abs:0.5:strict`` -> per-field ErrorBound."""
    name, _, rest = spec.partition("=")
    if not rest:
        raise argparse.ArgumentTypeError(f"bad --field-eb {spec!r}")
    parts = rest.split(":")
    kind = "rel"
    if parts[0] in ("rel", "abs"):
        kind = parts.pop(0)
    if not parts:
        raise argparse.ArgumentTypeError(
            f"bad --field-eb {spec!r}: missing bound value after {kind!r}")
    try:
        value = float(parts.pop(0))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"bad --field-eb {spec!r}: bound value must be a number")
    mode = parts.pop(0) if parts else None
    return name, repro.ErrorBound(rel=value if kind == "rel" else None,
                                  abs=value if kind == "abs" else None,
                                  mode=mode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="nyx",
                    choices=["nyx", "miranda", "hurricane"])
    ap.add_argument("--shape", default="32,48,48")
    ap.add_argument("--eb", type=float, default=1e-3,
                    help="default value-range-relative bound")
    ap.add_argument("--field-eb", action="append", default=[],
                    metavar="NAME=[rel:|abs:]VALUE[:MODE]",
                    help="per-field bound override (repeatable), e.g. "
                         "velocity_x=1e-2 or temperature=abs:0.5:relaxed")
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--mode", default="strict",
                    choices=["strict", "relaxed", "unregulated"])
    ap.add_argument("--compressor", default="szlike",
                    choices=registry.names(),
                    help="conventional stage (any registered compressor)")
    ap.add_argument("--list-compressors", action="store_true",
                    help="print the compressor registry and exit")
    ap.add_argument("--engine", default="batched",
                    choices=["serial", "batched", "streaming"],
                    help="batched = multi-field fused-dispatch engine; "
                         "streaming = bounded-memory pipeline + async "
                         "archive writer (both bit-identical to serial)")
    ap.add_argument("--lowering", default="auto",
                    choices=["eager", "jit", "pallas", "auto"],
                    help="kernel lowering for the hot path; non-eager "
                         "variants engage only where their byte-parity "
                         "probe passes, so archives are identical either "
                         "way (auto = fastest proven lowering)")
    ap.add_argument("--max-resident-mb", type=float, default=0.0,
                    help="streaming engine residency budget in MiB "
                         "(0 = track peak only, no ceiling)")
    ap.add_argument("--decode-field", default=None,
                    help="also time a lazy single-field random-access "
                         "decode of this field (streaming archives)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record telemetry and write a Chrome/Perfetto "
                         "trace_event JSON here (load it at ui.perfetto.dev);"
                         " PATH.jsonl gets the line-per-event log")
    ap.add_argument("--resume", action="store_true",
                    help="streaming engine: salvage a partial container at "
                         "--out left by a killed run (same config) and "
                         "compress only the remaining fields")
    ap.add_argument("--verify", action="store_true",
                    help="after compressing, re-read every entry through "
                         "the checksum path and report per-entry status")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    compile_cache.setup()

    if args.list_compressors:
        list_compressors()
        return

    shape = tuple(int(s) for s in args.shape.split(","))
    flds = F.make_fields(args.dataset, shape=shape, seed=0)
    cross = F.DEFAULT_CROSS_FIELD[args.dataset]
    try:
        bounds = dict(parse_field_eb(s) for s in args.field_eb)
    except argparse.ArgumentTypeError as exc:
        ap.error(str(exc))

    tel = repro.Telemetry() if args.trace_out else None
    sess = repro.NeurLZ(
        model=repro.ModelConfig(epochs=args.epochs, cross_field=cross),
        engine=repro.EngineConfig(
            engine=args.engine, compressor=args.compressor,
            lowering=args.lowering,
            max_resident_bytes=int(args.max_resident_mb * 2**20),
            telemetry=tel),
        regulation=repro.RegulationConfig(mode=args.mode))
    print(f"[compress] {args.dataset} {shape} eb={args.eb} mode={args.mode} "
          f"epochs={args.epochs} cross_field=on engine={args.engine} "
          f"lowering={args.lowering}"
          + (f" field_eb={ {n: (b.rel, b.abs, b.mode) for n, b in bounds.items()} }"
             if bounds else ""))
    path = args.out or os.path.join(
        tempfile.gettempdir(),
        f"{args.dataset}.nlzs" if args.engine == "streaming"
        else f"{args.dataset}.nlz")
    if args.resume and args.engine != "streaming":
        ap.error("--resume requires --engine streaming (the incremental "
                 "container is what a killed run leaves behind)")
    if args.engine == "streaming":
        # Full out-of-core path: incremental container straight to disk,
        # reopened as a *lazy* Archive handle (no field materializes until
        # decoded).
        arc = sess.compress_to(flds, path, bounds=bounds or None,
                               rel_eb=args.eb, resume=args.resume)
        report = arc.report
        nbytes = report["bytes_written"]
        if args.resume:
            done = report["resumed_fields"]
            print(f"[resume]   salvaged {len(done)} field"
                  f"{'s' if len(done) != 1 else ''} from the partial "
                  f"container" + (f": {', '.join(done)}" if done else ""))
        print(f"[resident] pipeline peak {report['peak_resident_bytes']/2**20:.2f} MiB"
              + (f" (budget {args.max_resident_mb:.2f} MiB)"
                 if args.max_resident_mb else " (no ceiling)")
              + f", writer busy {report['writer_busy_s']:.2f}s")
        if report["degraded_fields"]:
            print(f"[degraded] conv-only fallback (bound still honored): "
                  f"{', '.join(report['degraded_fields'])}")
    else:
        arc = sess.compress(flds, bounds=bounds or None, rel_eb=args.eb)
        nbytes = arc.save(path)
    if args.verify:
        rep = arc.verify()
        bad = {n: e for n, e in rep["entries"].items() if not e["ok"]}
        print(f"[verify]   {len(rep['entries'])} entries checksum-verified: "
              + ("all ok" if rep["ok"] else f"{len(bad)} FAILED {bad}"))
        assert rep["ok"], "container verification failed"
    cs = arc["timing"].get("conv_stage")
    if cs:
        print(f"[conv]     {cs['fields']} fields -> {cs['groups']} groups, "
              f"{cs['calls']} compressor calls "
              f"({cs['batched_fields']} batched / "
              f"{cs['fallback_fields']} per-field), {cs['conv_s']:.2f}s")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    rss_b = rss if sys.platform == "darwin" else rss * 1024
    print(f"[archive]  {path}  ({nbytes/2**20:.2f} MiB on disk, "
          f"process peak RSS {rss_b/2**20:.0f} MiB)")

    # Decode from disk to prove the round-trip (lazy open for streaming).
    with repro.Archive.open(path) as arc_disk:
        if args.decode_field:
            import time
            t0 = time.time()
            one = arc_disk.decode(args.decode_field)
            t1 = time.time() - t0
            reads = (len(arc_disk.reader.entry_reads)
                     if arc_disk.streaming else len(flds))
            print(f"[random]   decode({args.decode_field!r}) {t1*1e3:.0f} ms, "
                  f"{reads} entr{'y' if reads == 1 else 'ies'} read, "
                  f"{one.nbytes/2**20:.2f} MiB out")
        dec = sess.decompress(arc_disk)
    raw = sum(v.nbytes for v in flds.values())
    br = arc.bitrate()
    total = sum(br[n]["total_bytes"] for n in flds)
    print(f"[totals]   raw {raw/2**20:.1f} MiB -> {total/2**20:.2f} MiB "
          f"(CR {raw/total:.1f}x)")
    for name, x in flds.items():
        entry = arc["fields"][name]
        eb = entry["abs_eb"]
        mode = entry["mode"]
        err = np.abs(dec[name].astype(np.float64) - x.astype(np.float64)).max()
        conv = registry.decompress(entry["conv"])
        print(f"  {name:22s} [{mode:11s}] maxerr/eb={err/eb:6.3f}  "
              f"PSNR {metrics.psnr(x, conv):6.2f} -> {metrics.psnr(x, dec[name]):6.2f} dB  "
              f"bitrate {br[name]['bitrate']:6.3f} b/val")
        limit = eb if mode == "strict" else (
            2 * eb if mode == "relaxed" else np.inf)
        assert err <= limit * (1 + 1e-9), "bound violated!"
    print("[ok] all error bounds verified")

    if tel is not None:
        tel.export_chrome_trace(args.trace_out)
        tel.export_jsonl(args.trace_out + ".jsonl")
        s = tel.summary()
        top = sorted(s["spans"].items(), key=lambda kv: -kv[1]["wall_s"])[:6]
        print(f"[trace]    {args.trace_out} (+.jsonl): "
              f"{sum(a['count'] for _, a in top)} spans, top wall: "
              + ", ".join(f"{n} {a['wall_s']:.2f}s" for n, a in top))


if __name__ == "__main__":
    main()
