"""The comparison catches a broken timed path.  Each test skips the look
for a chip, drives the rest of a run at a small size on the CPU with one
fault planted in the program, and sees ``correct`` come out false; the
same run without a fault comes out true.  The limits are the cells' own."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import readings
import run
from conftest import tiny_cell
from harness import setup, spec

SEED = 2**31 + 101
LIMITS_1 = spec.read_json(setup.BENCH / "limits" / "photo-m3-train.json")[
    "limits"]
# no four-chip cell has limits of its own yet; the exchange fault is held
# to the one-chip cell's
LIMITS_4 = LIMITS_1


def one_run(cell):
    res = run.run(cell, SEED, seconds=0.3, traced=False)
    return res["correct"], res["compared"]


def test_sound_run_is_correct():
    ok, compared = one_run(tiny_cell(LIMITS_1))
    assert ok, compared


def test_state_left_unchanged_is_caught(monkeypatch):
    setup.use_program()
    from repro.core.parallel import ParallelADMMTrainer
    monkeypatch.setattr(ParallelADMMTrainer, "step", lambda self: None)
    ok, compared = one_run(tiny_cell(LIMITS_1))
    assert not ok
    assert compared["first_update"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(monkeypatch):
    to_program = setup.program_graph
    monkeypatch.setattr(setup, "program_graph",
                        lambda g: to_program(readings.half_batch(g)))
    ok, compared = one_run(tiny_cell(LIMITS_1))
    assert not ok, compared


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    setup.use_program()
    from repro.core.parallel import ParallelADMMTrainer
    step = ParallelADMMTrainer.step

    def altered(self):
        step(self)
        st = self.state
        self.state = st._replace(zs=st.zs[:-1] + (st.zs[-1] * 1.001,))
    monkeypatch.setattr(ParallelADMMTrainer, "step", altered)
    ok, compared = one_run(tiny_cell(LIMITS_1))
    assert not ok, compared


FOUR = """
import json, sys
sys.path[:0] = {paths!r}
from conftest import TINY_PROCEDURE, tiny_cell
import readings, run
for name, value in TINY_PROCEDURE.items():
    setattr(run, name, value)
cell = tiny_cell({limits!r}, chips=4, num_parts=4, fused=True)
with (readings.no_exchange() if {fault!r} else readings.contextlib.nullcontext()):
    res = run.run(cell, {seed!r}, seconds=0.3, traced=False)
print(json.dumps([res["correct"], res["compared"]]))
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_exchange_left_out_is_caught_on_four_devices(fault):
    code = FOUR.format(paths=[str(setup.BENCH), str(setup.BENCH / "tests")],
                       limits=LIMITS_4, fault=fault, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=setup.ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    ok, compared = json.loads(p.stdout.strip().splitlines()[-1])
    assert ok is (not fault), compared


def test_numbers_of_identical_runs_are_zero():
    from harness import compare
    st = {"w": [np.ones((2, 2))], "z": [np.ones((3, 2))], "u": np.zeros((3, 2))}
    moved = {"w": [np.full((2, 2), 2.0)], "z": [np.ones((3, 2)) * 3],
             "u": np.ones((3, 2))}
    vals, _ = compare.numbers([st, moved], [1.0], [st, moved], [1.0])
    assert vals == {"loss": 0.0, "first_update": 0.0, "change": 0.0}
