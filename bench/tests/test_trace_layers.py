"""``trace_layers.py``: the per-layer scope reduction on a made-up trace
against sums worked by hand, the per-search counters, and the tool end to
end on the CPU at the tiny size with three layers (the CPU profile has no
device planes, so only the counters have something to read)."""
import copy
import types

import jax
import pytest

import trace_layers
from conftest import tiny_cell

LIMITS = {"loss": 1.0, "first_update": 1.0, "change": 1.0}
SEED = 2**31 + 31
MS = 1e6   # ns


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(step)/admm_w/l2/transpose(admm_w)/l2/jvp()/dot", "admm_w/l2", True),
    ("jit(step)/admm_w/l2/while/body/dot", "admm_w/l1", False),
    ("jit(step)/admm_z/l2/dot", "admm_w/l2", False),
    ("jit(step)/admm_w/l12/dot", "admm_w/l1", False),
    ("jit(step)/admm_z/l1/admm_exchange/ppermute", "admm_exchange", True),
    ("jit(step)/admm_w", "admm_w/l1", False),
])
def test_a_scope_matches_whole_components_in_order(path, scope, inside):
    assert trace_layers.in_scope_path(path, scope) is inside


def made_up_trace():
    """Two devices, two rounds.  Device 0: W₁ 10 ms with a ``while`` inside
    it from 5 to 25 ms (union 25), W₂ 10, Z₁ 5 with an exchange of 2
    inside; device 1: W₁ 15, W₂ 10, Z₁ 7."""
    def ev(path, start, dur):
        return [f"jit(step)/{path}", start * MS, dur * MS]
    dev0 = [ev("admm_w/l1/dot", 0, 10), ev("admm_w/l1/while", 5, 20),
            ev("admm_w/l2/dot", 30, 10), ev("admm_z/l1/dot", 50, 5),
            ev("admm_z/l1/admm_exchange/collective-permute", 52, 2)]
    dev1 = [ev("admm_w/l1/dot", 0, 15), ev("admm_w/l2/dot", 30, 10),
            ev("admm_z/l1/dot", 50, 7), ev("admm_fista/dot", 60, 5)]
    return {"window": [0.0, 100 * MS], "host": [],
            "devices": {"0": {"ops": [], "scoped": dev0},
                        "1": {"ops": [], "scoped": dev1}}}


def test_split_by_hand():
    metrics = {"w_update_ms": 30.0, "z_update_ms": 6.0}
    out = trace_layers.split(made_up_trace(), 2, 2, metrics)
    assert out["layers"] == pytest.approx({
        "admm_w/l1": (25 + 15) / 2 / 2, "admm_w/l2": (10 + 10) / 2 / 2,
        "admm_z/l1": (5 + 7) / 2 / 2, "admm_exchange": (2 + 0) / 2 / 2})
    assert out["split_sum"]["admm_w"] == pytest.approx(
        {"layers": 15.0, "w_update_ms": 30.0})
    assert out["split_sum"]["admm_z"]["layers"] == pytest.approx(3.0)


def test_a_scope_with_no_ops_is_left_out():
    out = trace_layers.split(made_up_trace(), 2, 3, {})
    assert "admm_w/l3" not in out["layers"]
    assert "admm_z/l2" not in out["layers"]
    assert out["split_sum"] == {}


def test_search_counts_per_round_mean_over_shards():
    cols = ["evals", "capped", "w1.evals", "w1.capped", "z1.evals",
            "z1.capped"]
    counters = {"before": [[0, 0, 0, 0, 0, 0], [1, 0, 1, 0, 0, 0]],
                "after": [[8, 2, 4, 2, 4, 0], [11, 0, 7, 0, 4, 0]],
                "rounds": 2}
    assert trace_layers.search_counts(counters, cols) == {
        "w1": {"evals": 2.5, "capped": 0.5},
        "z1": {"evals": 2.0, "capped": 0.0}}
    two_columns = {"before": [[0, 0]], "after": [[8, 2]], "rounds": 2}
    assert trace_layers.search_counts(two_columns, cols) == {}


def test_three_layer_cell_on_the_cpu():
    cell = tiny_cell(LIMITS)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"]["layer_dims"] = [16, 32, 32, 4]
    dev = types.SimpleNamespace(device_kind="TPU v5 lite",
                                platform=jax.devices()[0].platform)
    res = trace_layers.trace_layers(cell, SEED, 2, [dev])
    assert res["layers"] == {} and res["split_sum"] == {}
    searches = res["searches"]
    assert sorted(searches) == ["w1", "w2", "w3", "z1", "z2", "z3"]
    assert all(s["evals"] >= 1 for s in searches.values())
    assert searches["z3"]["evals"] >= cell.config["admm"]["fista_iters"]
    metrics = res["program"]["metrics"]
    assert sum(s["evals"] for s in searches.values()) == \
        pytest.approx(metrics["ls_probes"])
    assert sum(s["capped"] for s in searches.values()) == \
        pytest.approx(metrics["ls_capped"])
