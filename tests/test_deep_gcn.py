"""Deep (3-layer) GCN ADMM: exercises the middle-layer ψ subproblem
(eq. 5, next layer hidden) in both serial and parallel trainers, which the
paper's 2-layer experiments never touch.

The parallel trainer at L = 3 is checked in each of its modes against two
references: the serial trainer's first W update (the global W objective is
the same in both), and three rounds of the benchmark's plain reference
(``bench/harness/reference.py``, the same community ADMM in float32
``jax.numpy``) through the benchmark's comparison
(``bench/harness/compare.py``).  The limits of that comparison are set as
``bench/tests/test_control.py`` sets them: between the packed trainer's
own readings and the control's (the reference computed in three bfloat16
passes), so the control comes out not correct and every mode has to come
as close to the reference as the packed trainer does.  Four-shard modes run
in a child process with four virtual CPU devices."""
import json
import math
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.serial import SerialADMMTrainer
from repro.core.subproblems import ADMMConfig
from repro.kernels import ops

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import compare, reference, sbm, setup  # noqa: E402

# the benchmark's test-size cell (bench/tests/conftest.py) at three layers
TINY_L3 = {
    "name": "tiny-sbm-l3",
    "data": {"nodes": 240, "avg_degree": 8.0, "features": 16, "classes": 4,
             "train": 60, "test": 60, "in_out_ratio": 12.0,
             "generator_seed": 0},
    "model": {"layer_dims": [16, 32, 32, 4], "activation": "relu"},
    "admm": {"nu": 1e-3, "rho": 1e-3, "tau_init": 1.0,
             "backtrack_growth": 2.0, "max_backtracks": 30,
             "fista_iters": 8, "backtrack_rtol": 1e-6},
    "precision": {"dtype": "float32", "matmul": "highest"},
}
NUM_PARTS = 4            # one community per shard on four shards
ROUNDS = 3
SEEDS = [2**31 + 5, 77]

# id -> (TrainerConfig, shards, Pallas kernel bodies in interpret mode)
MODES = {
    "packed-einsum": (TrainerConfig.packed(), 1, False),
    "packed-einsum-4": (TrainerConfig.packed(), 4, False),
    "packed-kernel": (TrainerConfig.packed(use_kernel=True), 1, True),
    "packed-fused": (TrainerConfig.packed(use_kernel=True, fused=True), 1,
                     True),
    "packed-kernel-4": (TrainerConfig.packed(use_kernel=True), 4, True),
    "packed-fused-4": (TrainerConfig.packed(use_kernel=True, fused=True),
                       4, True),
}
BASE = "packed-einsum"
# the first W update against the serial trainer's, as np.allclose
W_RTOL, W_ATOL = 2e-4, 2e-6


@pytest.fixture(scope="module")
def setup_mini():
    g = graph.synthetic_sbm("amazon_photo_mini", seed=2)
    cfg = gcn.GCNConfig(layer_dims=(745, 64, 32, 8))   # L = 3
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    return g, cfg, admm


@pytest.mark.slow
def test_serial_three_layer_learns(setup_mini):
    g, cfg, admm = setup_mini
    tr = SerialADMMTrainer(cfg, admm, g, seed=0)
    log = tr.train(20)
    assert log.train_acc[-1] > 0.5, log.train_acc
    assert np.isfinite(log.lagrangian).all()


# ---------------------------------------------------------------------------
# one mode's readings against the references
# ---------------------------------------------------------------------------

def tiny_cell():
    g = sbm.generate(TINY_L3["data"], seed=0)
    part, _ = setup.partition(TINY_L3["name"], g, NUM_PARTS, "multilevel")
    return g, part


def _host_state(trainer) -> dict:
    st, layout = trainer.state, trainer.layout

    def nodes(x):
        return layout.unpack(trainer.packed_layout.unpack_state(
            np.asarray(x)))
    return {"w": [np.asarray(w) for w in st.weights],
            "z": [nodes(z) for z in st.zs], "u": nodes(st.u),
            "tau": [float(t) for t in st.taus]}


def mode_readings(mode: str, seeds=SEEDS) -> dict:
    """Per seed: the first W update's largest gap from the serial
    trainer's, in units of ``W_RTOL`` · |W| + ``W_ATOL`` (under 1 passes),
    and ``compare.numbers`` of ``ROUNDS`` rounds against the
    reference's."""
    config, shards, interpret = MODES[mode]
    g, part = tiny_cell()
    cfg, admm = setup.program_configs(TINY_L3)
    g_prog = setup.program_graph(g)
    mesh = jax.make_mesh((shards,), (AXIS,), (AxisType.Auto,),
                         devices=jax.devices()[:shards])
    out = []
    ops.repro_force_interpret(interpret)
    try:
        with jax.default_matmul_precision("highest"):
            ref = reference.Reference(TINY_L3, g, part)
            for seed in seeds:
                trainer = ParallelADMMTrainer(
                    cfg, admm, g_prog, num_parts=NUM_PARTS, mesh=mesh,
                    seed=seed, part=part, config=config)
                states, losses = [_host_state(trainer)], []
                for _ in range(ROUNDS):
                    losses.append(float(trainer.train(1).lagrangian[-1]))
                    states.append(_host_state(trainer))
                serial = SerialADMMTrainer(cfg, admm, g_prog, seed=seed)
                serial.step()
                w_gap = max(
                    float(np.max(np.abs(np.asarray(ws) - wp)
                                 / (W_ATOL + W_RTOL * np.abs(np.asarray(ws)))))
                    for ws, wp in zip(serial.state.weights, states[1]["w"]))
                st = ref.initial(seed)
                ref_states, ref_losses = [reference.host(st)], []
                for _ in range(ROUNDS):
                    st = ref.step(st)
                    ref_states.append(reference.host(st))
                    ref_losses.append(ref.lagrangian(st))
                vals, _ = compare.numbers(states, losses, ref_states,
                                          ref_losses)
                out.append({"seed": seed, "w_gap": w_gap, **vals})
    finally:
        ops.repro_force_interpret(False)
    return {"mode": mode, "rows": out}


def control_readings(seeds=SEEDS) -> list:
    """The reference in three bfloat16 passes against the reference."""
    g, part = tiny_cell()
    rows = []
    with jax.default_matmul_precision("highest"):
        ref = reference.Reference(TINY_L3, g, part)
        ctl = reference.Reference(TINY_L3, g, part, precision="high")
        for seed in seeds:
            sides = []
            for r in (ctl, ref):
                st = r.initial(seed)
                states, losses = [reference.host(st)], []
                for _ in range(ROUNDS):
                    st = r.step(st)
                    states.append(reference.host(st))
                    losses.append(r.lagrangian(st))
                sides += [states, losses]
            rows.append(compare.numbers(*sides)[0])
    return rows


_FOUR_SHARD_WORKER = r"""
import json, sys
import test_deep_gcn as t
print("READINGS " + json.dumps([t.mode_readings(m) for m in sys.argv[1:]]))
"""


@pytest.fixture(scope="module")
def four_shard_readings():
    """The four-shard modes' readings, from one child process that sees
    four virtual CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    here = pathlib.Path(__file__).resolve().parent
    env["PYTHONPATH"] = os.pathsep.join([str(here.parent / "src"),
                                         str(here)])
    modes = [m for m, (_, shards, _) in MODES.items() if shards == 4]
    out = subprocess.run([sys.executable, "-c", _FOUR_SHARD_WORKER, *modes],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(x for x in out.stdout.splitlines()
                if x.startswith("READINGS "))
    return {r["mode"]: r for r in json.loads(line[len("READINGS "):])}


@pytest.fixture(scope="module")
def limits():
    """Limits between the packed trainer's readings and the control's, for
    the numbers the control separates by 3× or more."""
    lower = {k: max(r[k] for r in mode_readings(BASE)["rows"])
             for k in ("loss", "first_update", "change")}
    control = control_readings()
    worst = {k: min(r[k] for r in control) for k in lower}
    lim = {k: math.sqrt(lower[k] * worst[k]) for k in lower
           if worst[k] >= 3 * lower[k]}
    return lim, control


@pytest.mark.parametrize("mode", list(MODES))
def test_parallel_three_layer_matches_w_update(mode, limits,
                                               four_shard_readings):
    """Each mode's first W update agrees with the serial trainer's, and its
    first rounds with the reference's; the control's do not."""
    lim, control = limits
    assert lim, control
    for row in control:
        ok, compared = compare.judge(row, lim)
        assert not ok, compared
    shards = MODES[mode][1]
    res = four_shard_readings[mode] if shards == 4 else mode_readings(mode)
    assert [r["seed"] for r in res["rows"]] == SEEDS
    for row in res["rows"]:
        assert row["w_gap"] <= 1.0, row
        ok, compared = compare.judge(row, lim)
        assert ok, compared


@pytest.mark.slow
def test_parallel_three_layer_converges(setup_mini):
    g, cfg, admm = setup_mini
    p = ParallelADMMTrainer(cfg, admm, g, num_parts=3, seed=0)
    log = p.train(20)
    assert log.train_acc[-1] > 0.5, log.train_acc
