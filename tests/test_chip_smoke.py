"""``chip_smoke.py`` off the chip: its one-chip and four-chip runs on tiny
CPU graphs with the Pallas kernels in interpret mode, and the script
itself refusing to run (non-zero exit, no result line) when JAX finds no
TPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402

_TINY = """
def tiny(num_parts):
    from repro.core import gcn, graph
    from repro.core.subproblems import ADMMConfig
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=num_parts, nodes_per_part=16, attach=1, seed=0,
        feat_dim=8)
    return (g, part, gcn.GCNConfig(layer_dims=(8, 16, g.num_classes)),
            ADMMConfig(nu=1e-4, rho=1e-4))
"""
_ns: dict = {}
exec(_TINY, _ns)


@pytest.fixture
def tiny_run(monkeypatch):
    """The paper workload swapped for a tiny graph; kernels in interpret
    mode (CPU HLO has no tpu_custom_call, so the marker check is off)."""
    monkeypatch.setattr(chip_smoke, "paper_workload", _ns["tiny"])
    monkeypatch.setattr(chip_smoke, "KERNEL_MARK", "")
    kops.repro_force_interpret(True)
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        kops.repro_force_interpret(False)


def test_one_chip_run_on_cpu(tiny_run, capsys):
    chip_smoke.one_chip()
    out = capsys.readouterr().out
    for tag in ("ELL aggregation at C=16", "kernel vs einsum, stepwise",
                "kernel vs serial, stepwise", "own trajectory",
                "more steps through train()", "served vs dense forward"):
        assert tag in out, out


def test_four_chip_run_on_cpu_mesh():
    """The --chips 4 path on 4 virtual CPU devices: 4-shard fused trainer
    vs the same configuration on one device, stepwise."""
    code = _TINY + """
import sys, jax
sys.path.insert(0, {root!r})
import chip_smoke
chip_smoke.paper_workload = tiny
chip_smoke.KERNEL_MARK = ""
with jax.default_matmul_precision("highest"):
    chip_smoke.four_chips()
""".format(root=ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "4 shards vs 1 device, stepwise" in out.stdout, out.stdout
    assert "Z_1 plane rows per device" in out.stdout


def test_check_rejects_errors_above_tolerance():
    with pytest.raises(chip_smoke.PhaseError):
        chip_smoke.check("x", {"w": 0.0, "z": 1e-2, "lagrangian": 0.0},
                         1e-3)
    chip_smoke.check("x", {"w": 0.0, "z": 1e-4, "lagrangian": 0.0,
                           "theta_spread": 5.0}, 1e-3)
    assert chip_smoke.rel_err(np.ones(4), np.ones(4)) == 0.0


def test_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")
