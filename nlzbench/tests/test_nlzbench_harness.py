"""Finding cells, configurations, traffic and metrics by name; the device
check; the entry point's exit codes off the chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from _tiny import ROOT
from nlzbench import device, harness


def test_every_cell_resolves_and_every_metric_has_a_reader():
    bench = harness.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench, ROOT)
        assert cell.traffic["op"] in ("compress", "decode")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        harness.op_stream(cell.traffic["op"], ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"], ROOT).read)


def test_every_config_traffic_and_metric_file_is_named():
    """Each file of a configuration, a traffic mix or a metric reader is the
    one an entry of BENCHMARK.json names: none is left over unread."""
    bench = harness.load_benchmark(ROOT)
    nlz = ROOT / "nlzbench"
    assert {str(p.relative_to(ROOT)) for p in nlz.glob("configs/*.json")} \
        == {c["file"] for c in bench["configs"]}
    assert {p.stem for p in nlz.glob("traffic/*.json")} \
        == {w["traffic"] for w in bench["workloads"]}
    ops = {json.loads(p.read_text())["op"] for p in nlz.glob("traffic/*.json")}
    assert ops == {p.stem for p in nlz.glob("ops/*.py")} - {"__init__",
                                                            "common"}
    assert {p.stem for p in nlz.glob("metrics/*.py")
            if not p.stem.startswith("_")} \
        == {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}


def test_per_layer_metrics_move_a_metric_their_cells_report():
    bench = harness.load_benchmark(ROOT)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        for cell in m["workloads"]:
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    """A later cell adds a configuration, a traffic file and a metric reader
    as new files plus new BENCHMARK.json entries; nothing else changes."""
    shutil.copytree(ROOT / "nlzbench", tmp_path / "nlzbench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    bench = harness.load_benchmark(ROOT)
    cfg = json.loads((ROOT / "nlzbench/configs/nyx.json").read_text())
    cfg.update(name="nyx_tight", rel_eb=1e-4)
    (tmp_path / "nlzbench/configs/nyx_tight.json").write_text(json.dumps(cfg))
    (tmp_path / "nlzbench/traffic/compress_burst.json").write_text(
        json.dumps({"op": "compress", "snapshots": 3}))
    (tmp_path / "nlzbench/metrics/ops_per_window.py").write_text(
        "def read(run):\n    return len(run.done) or None\n")
    bench["configs"].append({"name": "nyx_tight", "source": "x",
                             "file": "nlzbench/configs/nyx_tight.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "nyx_tight.burst",
                               "config": "nyx_tight",
                               "traffic": "compress_burst", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "ops_per_window", "unit": "ops",
                               "better": "higher", "source": "host_clock",
                               "layer": "whole step",
                               "moves": "compress_MBps",
                               "workloads": ["nyx_tight.burst"]})
    for m in bench["end_to_end"]:
        if m["name"] == "compress_MBps":
            m["workloads"].append("nyx_tight.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("nyx_tight.burst", bench, tmp_path)
    assert cell.config["rel_eb"] == 1e-4 and cell.traffic["snapshots"] == 3
    assert [m["name"] for m in cell.per_layer] == ["ops_per_window"]
    reader = harness.metric_reader("ops_per_window", tmp_path)
    run = harness.Run(cell=cell, seed=1, setup_s=1.0, window=(0.0, 1.0),
                      ops=[harness.OpRecord(0, 0.0, 1.0, True)], answers=[])
    assert reader.read(run) == 1
    assert harness.read_metrics(cell.per_layer, run, tmp_path) == {
        "ops_per_window": {"value": 1, "unit": "ops"}}
    assert [m["name"] for m in cell.end_to_end] == ["compress_MBps",
                                                     "setup_s"]


def test_unknown_names_are_errors():
    bench = harness.load_benchmark(ROOT)
    with pytest.raises(harness.CellError):
        harness.find_cell("nyx.nonexistent", bench, ROOT)
    with pytest.raises(harness.CellError):
        harness.metric_reader("no_such_metric", ROOT)


def test_device_check_refuses_the_cpu():
    with pytest.raises(device.DeviceError, match="platform"):
        device.check(1, "tpu")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "nlzbench/run.py", "--workload", "nyx.compress",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_accelerator_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout == ""
    assert "platform 'cpu'" in p.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "nlzbench", tmp_path / "nlzbench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2 and p.stdout == ""


def test_result_line_keeps_the_sign_of_an_infinity():
    from nlzbench.run import finite
    line = json.dumps(finite({"checks": {
        "max_err_over_eb": {"value": float("inf"), "limit": 1.0},
        "enhancer_gain_db": {"value": float("-inf"), "limit": 0.01}}}),
        allow_nan=False)
    checks = json.loads(line)["checks"]
    assert checks["max_err_over_eb"]["value"] > 1.0
    assert checks["enhancer_gain_db"]["value"] < 0.01
