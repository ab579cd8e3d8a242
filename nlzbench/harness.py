"""The data-driven harness: find a cell's pieces by name, run its window,
reduce its trace, check its answers, and build the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* configuration ``<name>``  -> the ``file`` its ``configs`` entry names;
* traffic ``<name>``        -> ``nlzbench/traffic/<name>.json``, whose
  ``"op"`` names the op stream ``nlzbench/ops/<op>.py``;
* metric ``<name>``         -> ``nlzbench/metrics/<name>.py``, whose
  ``read(run)`` returns the value, or ``None`` when the run holds nothing
  for it to read (the metric is then left out of the line).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# Fixed and inside the checkout: the directory is where a later run of the
# same checkout must find the compiled programs again.
CACHE_DIR = BENCH_DIR / ".jax_cache"


class CellError(ValueError):
    """BENCHMARK.json, a configuration or a traffic file is malformed."""


def log(msg: str) -> None:
    print(f"[nlzbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding the pieces by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise CellError(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"nlzbench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    return _load_module(root / "nlzbench" / "metrics" / f"{name}.py",
                        f"metric_{name}")


def op_stream(op: str, root: Path = ROOT):
    return _load_module(root / "nlzbench" / "ops" / f"{op}.py", f"op_{op}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: dict, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in cfgs:
        raise CellError(f"{name}: no config {w['config']!r}")
    with open(root / cfgs[w["config"]]["file"]) as f:
        config = json.load(f)
    tpath = root / "nlzbench" / "traffic" / f"{w['traffic']}.json"
    if not tpath.is_file():
        raise CellError(f"{name}: no traffic file {tpath}")
    with open(tpath) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


# ---------------------------------------------------------------------------
# What a run leaves for the metric readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpRecord:
    """One completed (or failed) operation of the window."""

    index: int
    t0: float                   # perf_counter seconds
    t1: float
    ok: bool
    field_bytes: int = 0        # field data handed in or returned
    archive_bytes: int = 0      # archive bytes written
    values: int = 0             # field values handled
    error: str | None = None
    spans: list = dataclasses.field(default_factory=list)  # (name, t0, t1)
    info: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TraceView:
    """The traced window, on the profiler's clock (nanoseconds)."""

    dev: object                 # trace_reduce.DeviceTrace
    lo: float
    hi: float
    spans: list                 # (name, start_ns, end_ns): program + harness

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.dev.busy_ns(self.lo, self.hi) * 1e-9

    def span_intervals(self, name: str):
        from . import trace_reduce
        return trace_reduce.merge((s, e) for n, s, e in self.spans
                                  if n == name)


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    setup_s: float
    window: tuple[float, float]         # perf_counter seconds
    ops: list
    answers: list                       # per compared answer: quality dict
    peaks: dict | None = None
    trace: TraceView | None = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def done(self) -> list:
        return [r for r in self.ops if r.ok]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run_window(stream, seconds: float, *, trace: bool, log_dir=None):
    """Back-to-back ops until ``seconds`` have passed; the op in flight then
    finishes and counts.  Returns ``(records, (t0, t1), annotation host
    times)``."""
    import jax
    if trace:
        jax.profiler.start_trace(log_dir)
    records, marks = [], []
    t0 = time.perf_counter()
    i = 0
    while True:
        if trace:
            marks.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("nlzbench.op"):
                rec = stream.step(i)
        else:
            rec = stream.step(i)
        records.append(rec)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = records[-1].t1
    if trace:
        jax.profiler.stop_trace()
    return records, (t0, t1), marks


def build_trace_view(log_dir: str, window, marks, tel, tel_perf0: float,
                     records) -> TraceView:
    from . import trace_reduce
    dev = trace_reduce.DeviceTrace.from_file(trace_reduce.find_xplane(log_dir))
    starts = sorted(s for s, _ in dev.annotations.get("nlzbench.op", []))
    if len(starts) != len(marks):
        raise RuntimeError(f"{len(starts)} op annotations in the trace for "
                           f"{len(marks)} ops")
    clock = trace_reduce.ClockMap(zip(starts, marks))
    spans = []
    if tel is not None:
        for sp in tel.spans:
            t = tel_perf0 + sp.t0
            spans.append((sp.name, clock.ns(t), clock.ns(t + sp.dur)))
    for r in records:
        spans.append(("op", clock.ns(r.t0), clock.ns(r.t1)))
        for name, s, e in r.spans:
            spans.append((name, clock.ns(s), clock.ns(e)))
    return TraceView(dev=dev, lo=clock.ns(window[0]), hi=clock.ns(window[1]),
                     spans=spans)


def passes(check: dict) -> bool:
    """A compared number within its limit: at most the limit, or at least
    it where the check says ``"pass_if": ">="``."""
    if check.get("pass_if", "<=") == ">=":
        return check["value"] >= check["limit"]
    return check["value"] <= check["limit"]


def read_metrics(entries, run: Run, root: Path = ROOT) -> dict:
    out = {}
    for m in entries:
        value = metric_reader(m["name"], root).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(view: TraceView) -> dict:
    from . import trace_reduce
    gaps = view.dev.idle_gaps(view.lo, view.hi)
    return {"device_ops": [list(x) for x in view.dev.top_modules(view.lo,
                                                                 view.hi)],
            "idle_gaps": [list(x) for x in trace_reduce.attribute(
                gaps, view.spans)]}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process0: float, platform: str = "tpu",
             root: Path = ROOT) -> dict:
    """One run of one cell; returns the result line's object.  Raises
    :class:`device.DeviceError` when the chips the cell asks for are not
    there."""
    import repro.core  # noqa: F401  (sets x64 before any array is made)

    from . import device as device_lib

    dev = device_lib.check(cell.chips, platform)
    peaks = device_lib.peaks(dev["kind"]) if platform == "tpu" else None
    clock = device_lib.CompileClock()
    tel, tel_perf0 = None, 0.0
    if trace:
        import repro
        tel = repro.Telemetry(repro.TelemetryConfig(learning_traces=False))
        tel_perf0 = time.perf_counter()
    stream = op_stream(cell.traffic["op"], root).Stream(
        cell.config, cell.traffic, seed, telemetry=tel)
    with tempfile.TemporaryDirectory(prefix="nlzbench-") as tmp:
        stream.setup(tmp)
        c0, h0 = clock.mark()
        setup_s = time.perf_counter() - t_process0
        log(f"set-up {setup_s:.3f}s: {clock.compiles} compiles "
            f"({clock.compile_s:.3f}s), {clock.cache_hits} cache hits")
        records, window, marks = run_window(
            stream, seconds, trace=trace, log_dir=f"{tmp}/trace")
        c1, h1 = clock.mark()
        log(f"window {window[1] - window[0]:.3f}s: {len(records)} ops, "
            f"{c1 - c0} compiles, {h1 - h0} cache hits inside it")
        dev["memory_peak_bytes"] = device_lib.memory_peak_bytes(cell.chips)
        view = None
        if trace:
            t0 = time.perf_counter()
            view = build_trace_view(f"{tmp}/trace", window, marks, tel,
                                    tel_perf0, records)
            log(f"trace read in {time.perf_counter() - t0:.3f}s")
        t0 = time.perf_counter()
        answers, checks = stream.verify(records)
        stream.close()
        log(f"verify {time.perf_counter() - t0:.3f}s: {len(answers)} answers")
    run = Run(cell=cell, seed=seed, setup_s=setup_s, window=window,
              ops=records, answers=answers, peaks=peaks, trace=view)
    failed = [r for r in records if not r.ok]
    for r in failed:
        log(f"op {r.index} failed: {r.error}")
    correct = bool(answers) and all(passes(c) for c in checks.values())
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed)}
    if trace:
        result["metrics"] = read_metrics(cell.per_layer, run, root)
        dev["busy_s"] = view.busy_s
        dev["window_s"] = view.window_s
        result["device"] = dev
        result["breakdown"] = breakdown(view)
    else:
        result["metrics"] = read_metrics(cell.end_to_end, run, root)
        result["device"] = dev
    result["checks"] = checks
    return result
