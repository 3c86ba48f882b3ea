"""The interval arithmetic under every trace reduction, on hand-made
traces."""
import pytest

from harness import trace


def tr(devices, window=(0, 100), host=()):
    return {"window": list(window), "host": [list(h) for h in host],
            "devices": {d: {"ops": [list(e) for e in evs], "modules": []}
                        for d, evs in devices.items()}}


def test_merge_and_total():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.total([(0, 3), (5, 9)]) == 7


def test_subtract():
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 10)], [(-5, 20)]) == []
    assert trace.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]


def test_busy_clips_to_the_window_and_counts_overlaps_once():
    t = tr({"0": [("a", -10, 20), ("b", 5, 10), ("c", 90, 20)]})
    # [0,10) ∪ [5,15) ∪ [90,100) = 15 + 10 ns
    assert trace.busy_s(t) == pytest.approx(25e-9)
    assert trace.window_s(t) == pytest.approx(100e-9)


def test_busy_is_the_mean_over_devices():
    t = tr({"0": [("a", 0, 50)], "1": [("a", 0, 10)]})
    assert trace.busy_s(t) == pytest.approx(30e-9)


def test_exposed_is_what_no_other_op_covers():
    t = tr({"0": [("collective-permute-done", 10, 30), ("fusion", 20, 10)]})
    match = lambda n: "collective-permute" in n  # noqa: E731
    assert trace.time_in(t, "0", "ops", match) == pytest.approx(30e-9)
    assert trace.exposed_s(t, "0", match) == pytest.approx(20e-9)


def test_breakdown_ranks_ops_and_labels_gaps_by_host_span():
    t = tr({"0": [("k", 0, 30), ("k", 60, 10), ("f", 30, 5)]},
           host=[("train_chunk", 0, 100), ("PjitFunction(step)", 40, 10)])
    b = trace.breakdown(t)
    assert b["device_ops"][0] == ["k", pytest.approx(40e-9)]
    assert b["idle_gaps"][0] == ["train_chunk", pytest.approx(30e-9)]
    assert b["idle_gaps"][1] == ["train_chunk/PjitFunction(step)",
                                 pytest.approx(25e-9)]
