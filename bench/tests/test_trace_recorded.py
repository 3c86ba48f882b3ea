"""The per-layer metrics on a recorded trace: three whole ADMM rounds of
``photo-m3-train`` on one TPU v5e (``trace_photo_3_rounds.json.gz``, cut
from a ``run.py --trace 1 --save-trace`` run), reduced by the metric
readers and, independently, by plain sums over its events."""
import pathlib

import pytest

from harness import spec, trace, work

TRACE = trace.read_saved(str(pathlib.Path(__file__).with_name(
    "trace_photo_3_rounds.json.gz")))
PHOTO = {"n": 7650, "nnz": 2 * 119129 + 7650, "dims": [745, 1000, 8]}


def ctx(tr=TRACE, rounds=3, chips=1):
    return {"trace": tr, "rounds": rounds, "chips": chips,
            "peak": work.peaks("TPU v5 lite"), **PHOTO}


def read(metric, **kw):
    return spec.reader(metric).read(ctx(**kw))


def plain_sum(events, prefixes):
    t0, t1 = TRACE["window"]
    return sum(d for n, s, d in events
               if n.startswith(prefixes) and s >= t0 and s + d <= t1) / 1e9


def test_the_window_holds_three_rounds_of_three_programs():
    mods = [n.split("(")[0] for n, _, _ in TRACE["devices"]["0"]["modules"]]
    assert mods == ["jit_step", "jit_metrics", "jit_lagrangian"] * 3


def test_idle_share_against_a_plain_sweep():
    t0, t1 = TRACE["window"]
    busy, end = 0.0, t0
    for _, s, d in sorted(TRACE["devices"]["0"]["ops"], key=lambda e: e[1]):
        a, b = max(s, end), min(s + d, t1)
        if b > a:
            busy += b - a
        end = max(end, min(s + d, t1))
    expect = 100 * (1 - busy / (t1 - t0))
    assert read("idle_share") == pytest.approx(expect, rel=1e-9)
    assert 2 < expect < 20


def test_eval_ms_is_the_metrics_and_lagrangian_modules_per_round():
    expect = 1e3 * plain_sum(TRACE["devices"]["0"]["modules"],
                             ("jit_metrics", "jit_lagrangian")) / 3
    assert read("eval_ms") == pytest.approx(expect, rel=1e-9)
    assert 10 < expect < 30


def test_agg_kernel_ms_and_roofline():
    kernel_s = plain_sum(TRACE["devices"]["0"]["ops"], ("community_spmm",))
    assert read("agg_kernel_ms") == pytest.approx(1e3 * kernel_s / 3,
                                                  rel=1e-9)
    least = work.aggregation_least_s(PHOTO["n"], PHOTO["nnz"], PHOTO["dims"],
                                     work.peaks("TPU v5 lite"))
    assert read("agg_roofline") == pytest.approx(100 * least * 3 / kernel_s,
                                                 rel=1e-9)
    assert 0 < read("agg_roofline") < 100


def test_step_mfu_counts_model_flops_over_the_window():
    w = (TRACE["window"][1] - TRACE["window"][0]) / 1e9
    expect = 100 * 35.67e9 * 3 / (w * 197e12)
    assert read("step_mfu") == pytest.approx(expect, rel=1e-3)


def test_exchange_metrics_find_nothing_on_one_chip():
    assert read("exchange_ms") is None
    assert read("exchange_exposed_ms") is None


def test_readers_return_nothing_without_rounds_or_events():
    empty = {"window": TRACE["window"], "host": [],
             "devices": {"0": {"ops": [], "async": [], "modules": []}}}
    for m in ("eval_ms", "agg_kernel_ms", "agg_roofline", "step_mfu"):
        assert spec.reader(m).read(ctx(rounds=0)) is None, m
    for m in ("eval_ms", "agg_kernel_ms", "agg_roofline"):
        assert spec.reader(m).read(ctx(tr=empty)) is None, m


def test_breakdown_puts_the_aggregation_kernel_first():
    b = trace.breakdown(TRACE)
    assert b["device_ops"][0][0] == "community_spmm_ell"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(g[1] > 0 for g in b["idle_gaps"])
