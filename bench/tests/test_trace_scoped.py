"""The readers of the program's scopes, spans and counters on a recorded
trace: three ADMM rounds of ``photo-m3-train`` on one TPU v5e, written by
``trace_program.py --rounds 3 --save-trace``
(``trace_photo_3_rounds_scoped.json.gz``: ``trace.load``'s structure,
each device's ``scoped`` ops, the constructor's and ``train()``'s
recorded spans, and ``state.probes`` around the rounds), each against a
plain sweep over its events.  The harness's readers on the harness's own
recorded trace read what they read before the program had any of this."""
import pathlib

import pytest

import trace_program
from harness import spec, trace, work

HERE = pathlib.Path(__file__).parent
TRACE = trace.read_saved(str(HERE / "trace_photo_3_rounds_scoped.json.gz"))
OLD = trace.read_saved(str(HERE / "trace_photo_3_rounds.json.gz"))
PHOTO = {"n": 7650, "nnz": 2 * 119129 + 7650, "dims": [745, 1000, 8]}
ROUNDS = 3


def ctx(tr=TRACE):
    return {"trace": tr, "rounds": ROUNDS, "chips": 1,
            "peak": work.peaks("TPU v5 lite"), **PHOTO,
            "counters": TRACE["counters"], "spans": TRACE["setup_spans"]}


def read(metric, **kw):
    return spec.reader(metric).read(ctx(**kw))


def sweep_union(intervals):
    """Length of a union of [start, end) intervals, by a sorted sweep."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def in_window(events):
    t0, t1 = TRACE["window"]
    return [(max(s, t0), min(s + d, t1)) for _, s, d in events
            if min(s + d, t1) > max(s, t0)]


def test_the_window_holds_three_rounds_of_three_programs():
    mods = [n.split("(")[0] for n, _, _ in TRACE["devices"]["0"]["modules"]]
    assert mods == ["jit_step", "jit_metrics", "jit_lagrangian"] * 3
    assert TRACE["rounds"] == ROUNDS


@pytest.mark.parametrize("metric, scope", [
    ("w_update_ms", "admm_w"), ("z_update_ms", "admm_z"),
    ("fista_ms", "admm_fista"), ("dual_ms", "admm_dual")])
def test_scope_readers_against_a_plain_sweep(metric, scope):
    evs = [e for e in TRACE["devices"]["0"]["scoped"]
           if scope in e[0].split("/")]
    expect = sweep_union(in_window(evs)) / 1e6 / ROUNDS
    assert read(metric) == pytest.approx(expect, rel=1e-9)
    assert 0.5 < expect < 12


def test_the_four_scopes_make_up_the_step():
    step = sum(b - a for a, b in in_window(
        m for m in TRACE["devices"]["0"]["modules"]
        if m[0].startswith("jit_step"))) / 1e6 / ROUNDS
    parts = sum(read(m) for m in ("w_update_ms", "z_update_ms", "fista_ms",
                                  "dual_ms"))
    rest = trace_program.step_breakdown_ms(TRACE, ROUNDS)
    assert rest["jit_step_ms"] == pytest.approx(step, rel=1e-9)
    assert parts + rest["step_unscoped_ms"] == pytest.approx(step, rel=1e-3)
    assert 0.95 * step < parts <= step


def test_the_step_kernel_calls_sit_in_the_w_and_dual_scopes():
    kernels = [e[0] for e in TRACE["devices"]["0"]["scoped"]
               if e[0].startswith("jit(step)/") and "pallas_call" in e[0]]
    # XLA merges the step's repeated aggregations into three calls: Ã Z0
    # and Ã Z1, first met in the W update, and Ã Z1⁺ in the dual update
    scopes = sorted(p.split("/")[1] for p in kernels)
    assert scopes == sorted(["admm_dual", "admm_w", "admm_w"] * ROUNDS)


def test_sync_idle_against_a_plain_sweep():
    t0, t1 = TRACE["window"]
    idle, end = [], t0
    for a, b in sorted(in_window(TRACE["devices"]["0"]["ops"])):
        if a > end:
            idle.append((end, a))
        end = max(end, b)
    if end < t1:
        idle.append((end, t1))
    spans = in_window(h for h in TRACE["host"]
                      if h[0] in ("train.sync", "train.wait"))
    overlap = sum(max(0.0, min(b, d) - max(a, c))
                  for a, b in idle for c, d in spans)
    expect = overlap / 1e6 / ROUNDS
    assert read("sync_idle_ms") == pytest.approx(expect, rel=1e-9)
    idle_ms = sum(b - a for a, b in idle) / 1e6 / ROUNDS
    assert 0 < expect <= idle_ms
    assert idle_ms == pytest.approx(
        read("idle_share") / 100 * (t1 - t0) / 1e6 / ROUNDS, rel=1e-9)


def test_counters_per_round():
    before, after = TRACE["counters"]["before"], TRACE["counters"]["after"]
    assert read("ls_probes") == (after[0][0] - before[0][0]) / ROUNDS
    assert read("ls_capped") == (after[0][1] - before[0][1]) / ROUNDS
    # two W searches, one hidden-Z search and 8 FISTA searches a round
    assert read("ls_probes") >= 11


def test_setup_spans():
    (name_a, _, a0, a1), (name_b, _, b0, b1) = TRACE["setup_spans"]
    assert (name_a, name_b) == ("construct.layout", "construct.init_state")
    assert read("layout_s") == (a1 - a0) / 1e9
    assert read("init_state_s") == (b1 - b0) / 1e9
    assert a1 <= b0


def test_recorded_train_spans_sit_on_their_profiler_events():
    base = TRACE["profile_start_ns"]
    recorded = TRACE["train_spans"]
    events = sorted((h for h in TRACE["host"] if h[0].startswith("train.")),
                    key=lambda h: h[1])
    assert [e[0] for e in events] == [s[0] for s in recorded]
    assert len(recorded) == 4 * ROUNDS
    for (_, s, d), (_, _, a, b) in zip(events, recorded):
        assert abs(base + s - a) < 1e5 and abs(base + s + d - b) < 1e5


def test_named_kernels_still_match_the_aggregation_reader():
    names = {trace.op_kind(n) for n, _, _ in TRACE["devices"]["0"]["ops"]}
    assert "community_spmm_ell" in names
    assert 20 < read("agg_kernel_ms") < 35


@pytest.mark.parametrize("metric, value", [
    ("idle_share", 8.662080115764736), ("step_mfu", 0.47236061008304525),
    ("eval_ms", 18.52980033333333), ("agg_kernel_ms", 27.148881666666664),
    ("agg_roofline", 0.5311422086907436), ("exchange_ms", None),
    ("exchange_exposed_ms", None)])
def test_harness_readers_on_the_harness_trace_read_as_before(metric, value):
    got = spec.reader(metric).read(ctx(OLD))
    assert got == (value if value is None else pytest.approx(value,
                                                             rel=1e-12))
