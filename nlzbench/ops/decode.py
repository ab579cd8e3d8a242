"""Op stream ``decode``: a closed loop of whole-field decodes from archives
on disk, as an analysis job reads fields back one at a time.

Set-up makes ``traffic["snapshots"]`` successive snapshots of one evolving
stream drawn from ``traffic["data_seed"]`` (``traffic["step_rad"]`` apart,
see ``nlzbench/fields.py``), compresses each once with
``repro.NeurLZ(...).compress`` as the configuration states (``--seed``
seeds the enhancer's weights and its training order) and writes it with
``Archive.save``.  It keeps each original and each field's conventional
reconstruction on the host, and makes one warm-up request.

A request is ``repro.Archive.open(path)``, ``arc.decode(field)`` of one
whole field and the archive's close: the public random-access path, which
reads the field's entry and its aux fields' entries, decodes their
conventional payloads, runs the enhancer's inference and applies the
enhancement.  Requests cycle through the (snapshot, field) pairs in one
fixed order, the same for every seed, so a window repeats no input until
it holds more requests than pairs.

After the window every answer is compared with its original and with its
conventional reconstruction alone, and the first answer of each pair with
the plain reference's replay of the archive (``nlzbench/reference.py``).
"""
from __future__ import annotations

import os
import time

from nlzbench import fields as fields_lib
from nlzbench import quality
from nlzbench.harness import OpRecord, log
from nlzbench.ops import common


def conv_recs(path: str, names) -> dict:
    """Every field's conventional reconstruction, decoded as a request
    decodes it: the field with its aux fields, in one call."""
    import repro
    from repro.compressors import registry
    out: dict = {}
    with repro.Archive.open(path) as arc:
        for name in names:
            if name in out:
                continue
            want = [name] + list(arc.entry(name)["aux"])
            out.update(registry.decompress_many(
                {n: arc.entry(n)["conv"] for n in want}))
    return {n: out[n] for n in names}


def request(path: str, name: str, telemetry=None):
    """One request: open the archive, decode one whole field, close."""
    import repro
    with repro.Archive.open(path) as arc:
        if telemetry is not None:
            arc.telemetry = telemetry
        return arc.decode(name)


def replay_err_over_eb(path: str, name: str, got, recs: dict,
                       eb: float) -> float:
    """Worst distance of the answer from the reference's replay of the
    archived field, beyond the half ulp by which rounding to the answer's
    own dtype may move it, in units of ``eb``.  (The replay is float64; a
    float32 answer of a value near the field's largest already lies up to
    ~6e-5 eb from it, a floor that would hide a network computed in lower
    precision.)"""
    import numpy as np

    import repro
    from nlzbench import reference
    with repro.Archive.open(path) as arc:
        entry = arc.entry(name)
        slice_axis = arc["slice_axis"]
    ref = reference.replay(entry, recs[name],
                           [recs[a] for a in entry["aux"]], slice_axis)
    got = np.asarray(got)
    if got.shape != ref.shape:
        return float("inf")
    slack = np.abs(np.spacing(got)).astype(np.float64) / 2
    err = np.maximum(np.abs(got.astype(np.float64) - ref) - slack, 0.0)
    return float(err.max() / eb) if np.isfinite(err).all() else float("inf")


class Stream:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 telemetry=None):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.tel = telemetry

    def setup(self, workdir: str) -> None:
        cfg = self.config
        n = int(self.traffic["snapshots"])
        t0 = time.perf_counter()
        self.snaps = [fields_lib.snapshot(cfg["dataset"], common.shape(cfg),
                                          cfg["fields"],
                                          self.traffic["data_seed"], index=i,
                                          coupling=cfg["coupling"],
                                          step=self.traffic["step_rad"])
                      for i in range(n)]
        log(f"data: {n} snapshots x {len(cfg['fields'])} fields "
            f"{common.shape(cfg)} in {time.perf_counter() - t0:.3f}s")
        session = common.session(cfg, self.tel, self.seed)
        self.paths, self.recs = [], []
        for k, snap in enumerate(self.snaps):
            t0 = time.perf_counter()
            arc = session.compress(snap, rel_eb=cfg["rel_eb"])
            bad = common.degraded(arc)
            if bad:
                raise RuntimeError(f"snapshot {k}: degraded fields {bad}")
            path = os.path.join(workdir, f"snap{k}.nlz")
            nbytes = arc.save(path)
            del arc
            self.paths.append(path)
            self.recs.append(conv_recs(path, cfg["fields"]))
            log(f"snapshot {k}: compressed to {nbytes} B and conventional "
                f"reconstructions in {time.perf_counter() - t0:.3f}s")
        self.pairs = [(k, name) for k in range(n) for name in cfg["fields"]]
        self.answers: dict = {}
        t0 = time.perf_counter()
        rec = self.step(-1)
        self.answers.clear()
        if not rec.ok:
            raise RuntimeError(f"warm-up request failed: {rec.error}")
        log(f"warm-up request {time.perf_counter() - t0:.3f}s")

    def step(self, i: int) -> OpRecord:
        k, name = self.pairs[i % len(self.pairs)]
        x = self.snaps[k][name]
        info = {"snapshot": k, "field": name}
        t0 = time.perf_counter()
        try:
            y = request(self.paths[k], name, self.tel)
        except Exception as exc:  # a request that raises is a failed one
            return OpRecord(index=i, t0=t0, t1=time.perf_counter(), ok=False,
                            error=f"{type(exc).__name__}: {exc}", info=info)
        t1 = time.perf_counter()
        self.answers[i] = y
        return OpRecord(index=i, t0=t0, t1=t1, ok=True, field_bytes=x.nbytes,
                        values=x.size, info=info)

    def verify(self, records) -> tuple[list, dict]:
        """Every answer against its original and its conventional
        reconstruction; the first answer of each pair against the
        reference's replay."""
        cfg = self.config
        answers, replays, seen = [], [], set()
        for r in records:
            if r.index not in self.answers:
                continue
            k, name = r.info["snapshot"], r.info["field"]
            x, y = self.snaps[k][name], self.answers.pop(r.index)
            eb = quality.abs_bound(x, cfg["rel_eb"])
            a = common.answer(r.index, k, name, x, y, self.recs[k][name], eb)
            if (k, name) not in seen:
                seen.add((k, name))
                a["replay_err_over_eb"] = replay_err_over_eb(
                    self.paths[k], name, y, self.recs[k], eb)
                replays.append(a["replay_err_over_eb"])
            answers.append(a)
            log(f"request {r.index} snapshot {k} {name}: max_err/eb "
                f"{a['max_err_over_eb']:.9f}, mse/eb^2 "
                f"{a['mse_over_eb2']:.6f} (conv alone "
                f"{a['conv_mse_over_eb2']:.6f}), replay err/eb "
                f"{a.get('replay_err_over_eb', float('nan')):.3e}")
        return answers, common.decode_checks(answers, replays, len(records),
                                             cfg)

    def close(self) -> None:
        self.snaps, self.recs, self.answers = [], [], {}
