#!/usr/bin/env python3
"""Readings for the comparison that decides ``correct``: the program as
configured, and the controls and faults that must come out not correct.

    python3 nlzbench/control.py --workload nyx.compress \
        --control none --seeds 11,12,13 --control untrained --seeds 11,12

Each ``--control`` takes the ``--seeds`` that follow it; every seed is one
op of the cell, at the cell's own size, on a snapshot of the pool its
traffic draws (seed ``j`` of a list on snapshot ``1 + j % --snapshots`` of
a compress cell, whose snapshot 0 is its warm-up, and ``j % --snapshots``
of a decode cell).  In a decode cell an op is the compression of the
snapshot, then one request for each of its fields.  One process reads them
all, so the programs compile once.  Each reading prints one JSON line: the
numbers the cell compares, whether they pass their limits, and the
archive's bits per value.

``none``: the program as the configuration states it.

``reference_bf16``: the reference put in the program's place, in the
nearest precision below the configuration's float32: every answer is the
original field rounded to bfloat16.  No program runs.

``dnn_bf16``: the program compresses as configured, then decodes the
archive with the skipping DNN's channel contraction (``skipping_dnn._dot``,
the stacked tap windows against each layer's tap weights) fed in bfloat16
(a faster decode whose inference no longer matches the compressor's).

``bf16_both``: that contraction fed in bfloat16 at compress and at decode
alike.

``untrained``: training leaves the enhancer's state as it was (0 epochs).

``half_batch``: every training batch loses its second half; the loss is
the mean over the rest.

``conv_only``: the decode returns the conventional reconstruction, as a
decode that skips the enhancer would.

Exits nonzero without a TPU unless ``--platform`` says otherwise.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def dot_bf16(a, w):
    """``skipping_dnn._dot``'s contraction (``a``'s last axis against
    ``w``'s first) on bfloat16 inputs, accumulated in float32."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@contextlib.contextmanager
def bf16_dnn():
    """Every skipping-DNN channel contraction (``_dot``: each layer's tap
    weights ``[C_out, 9 C_in]`` against its stacked windows) on bfloat16
    inputs while inside."""
    import jax
    from repro.core import skipping_dnn

    orig = skipping_dnn._dot
    skipping_dnn._dot = dot_bf16
    jax.clear_caches()
    try:
        yield
    finally:
        skipping_dnn._dot = orig
        jax.clear_caches()


@contextlib.contextmanager
def half_batches():
    """Training batches cut to their first half while inside."""
    import jax
    from repro.core import online_trainer
    orig = online_trainer.epoch_batches

    def half(key, n, steps, batch):
        return orig(key, n, steps, batch)[:, : max(1, batch // 2)]

    online_trainer.epoch_batches = half
    jax.clear_caches()
    try:
        yield
    finally:
        online_trainer.epoch_batches = orig
        jax.clear_caches()


# Controls that patch the program for every op they read.
PATCHES = {"bf16_both": bf16_dnn, "half_batch": half_batches}


def _decode(path, *, conv_only=False):
    """``(decoded fields, conventional reconstructions)`` from the file."""
    import repro
    from repro.compressors import registry
    with repro.Archive.open(path) as arc:
        conv = registry.decompress_many(
            {n: arc.entry(n)["conv"] for n in arc.field_names})
        got = dict(conv) if conv_only else arc.decode_all(engine="batched")
    return got, conv


def pool_snapshot(cell, index: int) -> dict:
    """Snapshot ``index`` of the pool the cell's traffic draws."""
    from nlzbench import fields
    from nlzbench.ops import common
    cfg, tr = cell.config, cell.traffic
    return fields.snapshot(cfg["dataset"], common.shape(cfg), cfg["fields"],
                           tr["data_seed"], index=index,
                           coupling=cfg["coupling"], step=tr["step_rad"])


def decode_reading(cell, control: str, seed: int, snap: dict, index: int,
                   workdir: str) -> dict:
    """One op of a decode cell under ``control``: the snapshot compressed
    with the enhancer seeded by ``seed``, then one request per field, each
    compared as the cell compares its requests.  Controls that differ only
    at decode read the archive an earlier reading of the same seed, snapshot
    and epochs wrote in ``workdir``."""
    from nlzbench import harness, quality
    from nlzbench.ops import common, decode
    if control == "reference_bf16":
        raise ValueError("reference_bf16 reads compress cells only")
    cfg = dict(cell.config)
    if control == "untrained":
        cfg["epochs"] = 0
    path = os.path.join(workdir, f"d{seed}_{index}_{cfg['epochs']}.nlz")
    if not os.path.exists(path):
        arc = common.session(cfg, seed=seed).compress(snap,
                                                      rel_eb=cfg["rel_eb"])
        arc.save(path)
        del arc
    nbytes = os.path.getsize(path)
    recs = decode.conv_recs(path, cfg["fields"])
    answers, replays = [], []
    for name in cfg["fields"]:
        if control == "conv_only":
            got = recs[name]
        elif control == "dnn_bf16":
            with bf16_dnn():
                got = decode.request(path, name)
        else:
            got = decode.request(path, name)
        x = snap[name]
        eb = quality.abs_bound(x, cfg["rel_eb"])
        a = common.answer(1, index, name, x, got, recs[name], eb)
        a["replay_err_over_eb"] = decode.replay_err_over_eb(path, name, got,
                                                            recs, eb)
        replays.append(a["replay_err_over_eb"])
        answers.append(a)
    checks = common.decode_checks(answers, replays, len(cfg["fields"]), cfg)
    return {"seed": seed, "control": control, "snapshot": index,
            "bits_per_value": 8.0 * nbytes / sum(x.size
                                                 for x in snap.values()),
            "answers": answers, "checks": checks,
            "correct": all(harness.passes(c) for c in checks.values())}


def reading(cell, control: str, seed: int, snap: dict, index: int,
            workdir: str) -> dict:
    """One op of the cell under ``control``; the answers it compares.  The
    program-wide patches of ``bf16_both`` and ``half_batch`` are the
    caller's (:data:`PATCHES`), so a run of readings compiles them once."""
    import jax.numpy as jnp
    import numpy as np

    from nlzbench import harness, quality
    from nlzbench.ops import common
    cfg = dict(cell.config)
    if control == "untrained":
        cfg["epochs"] = 0
    bits = None
    if control == "reference_bf16":
        got = {n: np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                             .astype(jnp.float32)) for n, x in snap.items()}
        conv = got
    else:
        path = os.path.join(workdir, f"r{seed}.nlz")
        arc = common.session(cfg, seed=seed).compress(snap,
                                                      rel_eb=cfg["rel_eb"])
        nbytes = arc.save(path)
        if control != "dnn_bf16":
            got, conv = _decode(path, conv_only=control == "conv_only")
        else:
            with bf16_dnn():
                got, conv = _decode(path)
        bits = 8.0 * nbytes / sum(x.size for x in snap.values())
    answers = [common.answer(1, index, n, x, got.get(n), conv[n],
                             quality.abs_bound(x, cfg["rel_eb"]))
               for n, x in snap.items()]
    checks = common.checks(answers, 1, cfg)
    return {"seed": seed, "control": control, "snapshot": index,
            "bits_per_value": bits, "answers": answers, "checks": checks,
            "correct": all(harness.passes(c) for c in checks.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", action="append", required=True,
                    choices=("none", "reference_bf16", "dnn_bf16",
                             "bf16_both", "untrained", "half_batch",
                             "conv_only"))
    ap.add_argument("--seeds", action="append", required=True)
    ap.add_argument("--snapshots", type=int, default=2,
                    help="readings rotate over the pool's first snapshots")
    ap.add_argument("--deadline", type=float, default=None,
                    help="unix time after which no reading starts")
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    if len(args.control) != len(args.seeds):
        ap.error("give one --seeds list after each --control")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import tempfile

    from nlzbench import harness
    from nlzbench.run import setup_compile_cache

    # Before anything imports the program: its import compiles, and JAX
    # keeps the cache directory that its first compile found.
    setup_compile_cache(harness.CACHE_DIR)
    import repro.core  # noqa: F401
    from nlzbench import device
    cell = harness.find_cell(args.workload, harness.load_benchmark(ROOT),
                             ROOT)
    try:
        dev = device.check(cell.chips, args.platform)
    except device.DeviceError as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 3
    decoding = cell.traffic["op"] == "decode"
    first, read = (0, decode_reading) if decoding else (1, reading)
    pool = {}
    with tempfile.TemporaryDirectory(prefix="nlzbench-control-") as tmp:
        for control, seeds in zip(args.control, args.seeds):
            with PATCHES.get(control, contextlib.nullcontext)():
                for j, seed in enumerate(int(s) for s in seeds.split(",")):
                    if args.deadline is not None \
                            and time.time() > args.deadline:
                        print(f"control: deadline reached before {control} "
                              f"seed {seed}", file=sys.stderr, flush=True)
                        return 0
                    index = first + j % args.snapshots
                    if index not in pool:
                        pool[index] = pool_snapshot(cell, index)
                    t0 = time.perf_counter()
                    out = read(cell, control, seed, pool[index], index, tmp)
                    out["seconds"] = time.perf_counter() - t0
                    out["device"] = dev
                    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
