"""chip_smoke.py's phases on the CPU at 8×16×16 (the chip run is the same
code at 512³), its refusal to run without a TPU, and the compile-cache
helper every entry point calls."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import dispatch

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

SMALL = ["--shape", "8,16,16", "--epochs", "1"]


def test_phases_pass_on_cpu(capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke.tempfile, "tempdir", str(tmp_path))
    dev = chip_smoke.run(chip_smoke.parse_args(SMALL), platform="cpu")
    assert dev == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
    out = capsys.readouterr().out
    for phase in ("device", "kernels", "compress", "decode", "serve",
                  "total"):
        assert f"[{phase}]" in out, phase
    assert "bit-identical to Archive.decode: True" in out
    assert "resolved dnn_forward: auto -> jit" in out
    assert list(tmp_path.iterdir()) == []          # archive dir removed


def test_main_refuses_cpu(capsys, monkeypatch):
    # Leave the process's cache config alone: with the variable set the
    # helper touches nothing.
    monkeypatch.setenv(compile_cache.ENV_VAR, str(ROOT / ".jax_cache"))
    assert chip_smoke.main(SMALL) != 0
    out, err = capsys.readouterr()
    assert "refusing to run elsewhere" in err
    assert '"ok"' not in out


def test_main_refuses_a_directory_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_kernels_phase_fails_only_on_a_raised_probe():
    def boom():
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    dispatch.register("_smoke_op", "eager", lambda: "eager")
    dispatch.register("_smoke_op", "jit", lambda: "jit",
                      probe=lambda: False)           # mismatch: allowed
    try:
        report = chip_smoke.phase_kernels()
        assert report["_smoke_op"]["jit"] == f"fallback ({dispatch.MISMATCH})"
        dispatch.register("_smoke_op", "pallas", lambda: "pallas",
                          probe=boom)
        with pytest.raises(chip_smoke.SmokeFailure, match="_smoke_op/pallas"):
            chip_smoke.phase_kernels()
    finally:
        dispatch._ops.pop("_smoke_op", None)
        dispatch.clear_cache()


def test_device_phase_wants_the_platform_and_count():
    with pytest.raises(chip_smoke.SmokeFailure, match="refusing"):
        chip_smoke.phase_device("tpu")
    with pytest.raises(chip_smoke.SmokeFailure, match="devices, want"):
        chip_smoke.phase_device(jax.devices()[0].platform,
                                count=len(jax.devices()) + 1)


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 path on four virtual CPU devices (a fresh process: the
    device count is fixed when the backend starts)."""
    code = ("import sys, json; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "import chip_smoke as cs\n"
            "args = cs.parse_args(['--chips', '4', '--shape', '8,16,16',"
            " '--epochs', '1'])\n"
            "print(json.dumps(cs.run(args, platform='cpu')))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code, str(ROOT),
                        str(ROOT / "src")], env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1])["count"] == 4
    assert any("archives byte-identical: True" in ln for ln in lines)
    placed = [ln for ln in lines if "sharded: " in ln][0]
    assert "[0]" in placed and "[1]" in placed     # one group per device


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    prev = jax.config.jax_compilation_cache_dir
    try:
        if env_dir:
            monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
            assert compile_cache.setup() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev  # untouched
        else:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
            want = str(ROOT / ".jax_cache")
            assert compile_cache.setup() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
