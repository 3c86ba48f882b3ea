"""A run that cannot measure the chip exits non-zero and prints no
result: no TPU, too few chips, a device kind with no known peaks, or a
checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys
import types

import pytest

import run
from harness import setup

ARGS = ["--workload", "photo-m3-train", "--seed", "2147483653",
        "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run([sys.executable, str(setup.BENCH / "run.py"), *ARGS],
                       cwd=setup.ROOT, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(setup.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(setup.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=tmp_path,
                       env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _fake_devices(monkeypatch, kind, count):
    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev] * count)


@pytest.mark.parametrize("kind, count, chips", [
    ("TPU v99", 1, 1),          # no peaks for this kind
    ("TPU v5 lite", 1, 4),      # fewer chips than the cell asks for
])
def test_device_checks_refuse(monkeypatch, kind, count, chips):
    _fake_devices(monkeypatch, kind, count)
    assert run.devices_for(chips) is None


def test_unknown_device_kind_exits_nonzero_without_a_result(monkeypatch,
                                                            capsys):
    _fake_devices(monkeypatch, "TPU v99", 1)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    assert run.main(ARGS) != 0
    assert capsys.readouterr().out.strip() == ""


def test_known_chip_is_accepted(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", 4)
    assert len(run.devices_for(4)) == 4
