"""``trace_program.py`` end to end on the CPU at the tiny size: the
constructor's spans, the counters around the traced rounds, the recorded
spans against their profiler events, and the saved trace.  The CPU
profile has no device planes, so the device readers find nothing."""
import gzip
import json
import types

import jax
import pytest

import trace_program
from conftest import tiny_cell

LIMITS = {"loss": 1.0, "first_update": 1.0, "change": 1.0}
SEED = 2**31 + 29


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("traced") / "t.json.gz"
    dev = types.SimpleNamespace(device_kind="TPU v5 lite",
                                platform=jax.devices()[0].platform)
    return trace_program.trace_cell(tiny_cell(LIMITS), SEED, 2, [dev],
                                    save_trace=str(out)), out


def test_constructor_spans_split_the_constructor(traced):
    res, _ = traced
    m = res["metrics"]
    assert m["layout_s"] > 0 and m["init_state_s"] > 0
    assert m["layout_s"] + m["init_state_s"] <= res["construct_s"]


def test_counters_count_every_search_of_the_traced_rounds(traced):
    res, _ = traced
    c = res["counters"]
    assert c["rounds"] == 2
    # 2 W searches, 1 hidden-Z search, 8 FISTA searches, each at least
    # one evaluation
    assert res["metrics"]["ls_probes"] >= 11
    assert res["metrics"]["ls_capped"] >= 0
    assert c["after"][0][0] - c["before"][0][0] == \
        2 * res["metrics"]["ls_probes"]


def test_recorded_spans_sit_on_their_profiler_events(traced):
    res, _ = traced
    offs = res["span_offset_ms"]
    assert set(offs) == {"train.step", "train.wait", "train.eval",
                         "train.sync"}
    for name, o in offs.items():
        assert o["start"] < 0.1 and o["end"] < 0.1, (name, o)


def test_device_readers_find_nothing_without_device_planes(traced):
    res, _ = traced
    for m in ("w_update_ms", "sync_idle_ms", "idle_share", "eval_ms"):
        assert m not in res["metrics"], m


def test_saved_trace_holds_spans_and_counters(traced):
    res, path = traced
    with gzip.open(path, "rt") as fh:
        saved = json.load(fh)
    assert saved["rounds"] == 2
    assert saved["counters"] == res["counters"]
    assert [s[0] for s in saved["setup_spans"]] == [
        "construct.layout", "construct.init_state"]
    assert len(saved["train_spans"]) == 8
    assert saved["profile_start_ns"] > 0


def test_idle_by_span_splits_the_idle_time():
    ops = [("k", 0, 20), ("k", 50, 30)]           # idle [20,50), [80,100)
    host = [("train.step", 20, 5), ("train.sync", 30, 30),
            ("train.wait", 85, 5), ("train_chunk", 0, 100)]
    t = {"window": [0, 100], "host": [list(h) for h in host],
         "devices": {"0": {"ops": [list(e) for e in ops], "modules": []}}}
    got = trace_program.idle_by_span_ms({"trace": t, "rounds": 1})
    assert got == pytest.approx({"train.step": 5e-6, "train.wait": 5e-6,
                                 "train.eval": 0.0, "train.sync": 20e-6,
                                 "none": 20e-6})
