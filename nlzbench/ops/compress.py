"""Op stream ``compress``: a closed loop of whole-snapshot compressions.

Each op hands one snapshot to ``repro.NeurLZ(...).compress`` and writes the
archive with ``Archive.save``, as a simulation that emits a snapshot and
waits for it to be archived before the next.  The snapshots are successive
steps of one evolving stream drawn from ``traffic["data_seed"]``
(``traffic["step_rad"]`` per snapshot, see ``nlzbench/fields.py``): index
0 for the warm-up op, then ``traffic["snapshots"]`` more, handed over in
time order, so no op of a window repeats an input until the window holds
more ops than the pool.  Every seed compresses the same snapshots, and
``--seed`` seeds the enhancer's weights and its training order: the work of
a run does not depend on the seed (the value range of independent draws,
which sets the bound and the bits, varies widely between them).  After the
window the first archive written for each snapshot is opened and decoded,
and each field is compared with its snapshot and with the archive's
conventional reconstruction alone.
"""
from __future__ import annotations

import os
import time

from nlzbench import fields as fields_lib
from nlzbench import quality
from nlzbench.harness import OpRecord, log
from nlzbench.ops import common


class Stream:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 telemetry=None):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.tel = telemetry

    def setup(self, workdir: str) -> None:
        cfg = self.config
        self.dir = workdir
        n = int(self.traffic["snapshots"])
        t0 = time.perf_counter()
        self.snaps = [fields_lib.snapshot(cfg["dataset"], common.shape(cfg),
                                          cfg["fields"],
                                          self.traffic["data_seed"], index=i,
                                          coupling=cfg["coupling"],
                                          step=self.traffic["step_rad"])
                      for i in range(n + 1)]
        log(f"data: {n + 1} snapshots x {len(cfg['fields'])} fields "
            f"{common.shape(cfg)} in {time.perf_counter() - t0:.3f}s")
        self.session = common.session(cfg, self.tel, self.seed)
        t0 = time.perf_counter()
        rec = self._op(-1, self.snaps[0])
        if not rec.ok:
            raise RuntimeError(f"warm-up op failed: {rec.error}")
        log(f"warm-up op {time.perf_counter() - t0:.3f}s")

    def _op(self, index: int, snap: dict) -> OpRecord:
        path = os.path.join(self.dir, f"op{index}.nlz")
        t0 = time.perf_counter()
        try:
            arc = self.session.compress(snap, rel_eb=self.config["rel_eb"])
            t_save = time.perf_counter()
            nbytes = arc.save(path)
            t1 = time.perf_counter()
        except Exception as exc:  # an op that raises is a failed op
            return OpRecord(index=index, t0=t0, t1=time.perf_counter(),
                            ok=False, error=f"{type(exc).__name__}: {exc}")
        bad = common.degraded(arc)
        return OpRecord(
            index=index, t0=t0, t1=t1, ok=not bad,
            error=f"degraded fields {bad}" if bad else None,
            field_bytes=sum(x.nbytes for x in snap.values()),
            archive_bytes=nbytes, values=sum(x.size for x in snap.values()),
            spans=[("save", t_save, t1)], info={"path": path})

    def step(self, i: int) -> OpRecord:
        k = 1 + i % (len(self.snaps) - 1)
        rec = self._op(i, self.snaps[k])
        rec.info["snapshot"] = k
        return rec

    def verify(self, records) -> tuple[list, dict]:
        """Decode the first archive the window wrote for each snapshot, from
        its file, and compare every field with the snapshot and with the
        archive's conventional reconstruction."""
        import repro
        from repro.compressors import registry
        first = {}
        for r in records:
            if "path" in r.info:
                first.setdefault(r.info["snapshot"], r)
        answers = []
        for r in sorted(first.values(), key=lambda r: r.index):
            snap = self.snaps[r.info["snapshot"]]
            try:
                with repro.Archive.open(r.info["path"]) as arc:
                    got = arc.decode_all(engine="batched")
                    conv = registry.decompress_many(
                        {n: arc.entry(n)["conv"] for n in arc.field_names})
            except Exception as exc:  # an archive that will not decode
                log(f"op {r.index}: archive does not decode: "
                    f"{type(exc).__name__}: {exc}")
                continue
            for name, x in snap.items():
                eb = quality.abs_bound(x, self.config["rel_eb"])
                answers.append(common.answer(
                    r.index, r.info["snapshot"], name, x, got.get(name),
                    conv.get(name, x), eb))
        for a in answers:
            log(f"op {a['op']} {a['field']}: max_err/eb "
                f"{a['max_err_over_eb']:.9f}, mse/eb^2 "
                f"{a['mse_over_eb2']:.6f} (conv alone "
                f"{a['conv_mse_over_eb2']:.6f})")
        return answers, common.checks(answers, len(first), self.config)

    def close(self) -> None:
        self.snaps = []
