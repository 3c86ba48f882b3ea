"""The control, at a size a test run holds: the reference computed one
precision below the configuration's (three bfloat16 passes for float32 at
``highest``), put in the program's place.  On the chip, at the cells' own
sizes, it reads above the cells' limits (``bench/limits``); here, at 240
nodes, its readings are smaller, so the test sets limits from its own
sound and control readings by the same rule and sees the control come out
not correct and the program correct.  The reference started from another
summation order stands for a sound rewrite: it passes the cells' limits."""
import math

import pytest

import readings
from conftest import tiny_cell
from harness import compare, setup

SEEDS = [2**31 + 5, 77, 123456]


@pytest.fixture(scope="module")
def res():
    return readings.readings(tiny_cell({}), SEEDS, n_control=3, n_faults=0,
                             n_reassociated=3, say=lambda _: None)


def test_control_is_caught_and_the_program_is_not(res):
    lower, control = res["summary"]["lower"], res["summary"]["control"]
    # numbers the control separates by 3× or more get a limit between the
    # two readings; the others are not compared
    limits = {k: math.sqrt(lower[k] * control[k]) for k in lower
              if control[k] >= 3 * lower[k]}
    assert limits, (lower, control)
    for row in res["control"]:
        ok, compared = compare.judge(row, limits)
        assert not ok, compared
    for row in res["sound"]:
        ok, compared = compare.judge(row, limits)
        assert ok, compared


@pytest.mark.parametrize("cell", sorted(
    p.stem for p in (setup.BENCH / "limits").glob("*.json")))
def test_a_reordered_sum_passes_the_cells_limits(res, cell):
    limits = setup.read_json(setup.BENCH / "limits" / f"{cell}.json")[
        "limits"]
    assert len(res["reassociated"]) == len(SEEDS)
    for row in res["reassociated"]:
        ok, compared = compare.judge(row, limits)
        assert ok, compared
