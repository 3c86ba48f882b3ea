"""The readers of what the program records about itself (scopes, host
spans, counters, set-up spans) on hand-made traces, on the recorded trace
of the harness (which holds none of it), and ``harness.xspace`` on a
hand-made profile."""
import pathlib

import pytest

from harness import spec, trace, xspace

OLD = trace.read_saved(str(pathlib.Path(__file__).with_name(
    "trace_photo_3_rounds.json.gz")))
NEW = ("w_update_ms", "z_update_ms", "fista_ms", "dual_ms", "sync_idle_ms",
       "ls_probes", "ls_capped", "layout_s", "init_state_s")


def tr(scoped, ops=(), host=(), window=(0, 100)):
    return {"window": list(window), "host": [list(h) for h in host],
            "devices": {d: {"ops": [list(e) for e in ops], "modules": [],
                            "scoped": [list(e) for e in evs]}
                        for d, evs in scoped.items()}}


def read(metric, t, rounds=1, **kw):
    return spec.reader(metric).read({"trace": t, "rounds": rounds, **kw})


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_find_nothing_in_the_harness_trace(metric):
    ctx = {"trace": OLD, "rounds": 3, "chips": 1}
    assert spec.reader(metric).read(ctx) is None


def test_scope_time_counts_a_loop_and_its_body_once():
    t = tr({"0": [("jit(step)/admm_w/while", 10, 40),
                  ("jit(step)/admm_w/while/body/dot_general", 15, 10),
                  ("jit(step)/admm_w/mul", 60, 5),
                  ("jit(step)/admm_z/while", 70, 20),
                  ("jit(step)/admm_wx/add", 95, 1)]})
    assert read("w_update_ms", t) == pytest.approx(45e-6)
    assert read("z_update_ms", t) == pytest.approx(20e-6)
    assert read("w_update_ms", t, rounds=5) == pytest.approx(9e-6)
    assert read("fista_ms", t) is None


def test_scope_time_is_clipped_and_averaged_over_devices():
    t = tr({"0": [("a/admm_dual/x", -10, 20)],
            "1": [("a/admm_dual/x", 90, 30)]})
    assert read("dual_ms", t) == pytest.approx(10e-6)


def test_scope_readers_need_every_device_scoped():
    t = tr({"0": [("a/admm_fista/x", 0, 10)]})
    del t["devices"]["0"]["scoped"]
    assert read("fista_ms", t) is None
    assert read("fista_ms", tr({"0": [("a/admm_fista/x", 0, 10)]}),
                rounds=0) is None


def test_sync_idle_is_idle_time_under_the_sync_and_wait_spans():
    # busy [0,20) and [50,80); idle [20,50) and [80,100)
    ops = [("k", 0, 20), ("k", 50, 30)]
    host = [("train.sync", 30, 30), ("train.wait", 85, 5),
            ("train.eval", 20, 10), ("np.asarray(jax.Array)", 0, 100)]
    t = tr({"0": []}, ops=ops, host=host)
    # idle under train.sync: [30,50) = 20; under train.wait: [85,90) = 5
    assert read("sync_idle_ms", t) == pytest.approx(25e-6)
    assert read("sync_idle_ms", t, rounds=5) == pytest.approx(5e-6)
    t_none = tr({"0": []}, ops=ops, host=host[2:])
    assert read("sync_idle_ms", t_none) is None


def test_counters_per_round_mean_over_shards():
    c = {"before": [[10, 0], [12, 0]], "after": [[40, 3], [52, 3]],
         "rounds": 2}
    ctx = {"counters": c}
    assert spec.reader("ls_probes").read(ctx) == pytest.approx(17.5)
    assert spec.reader("ls_capped").read(ctx) == pytest.approx(1.5)
    assert spec.reader("ls_probes").read(
        {"counters": dict(c, rounds=0)}) is None


def test_setup_spans_by_name():
    rows = [["construct.layout", None, 1_000, 2_500_001_000],
            ["construct.init_state", None, 2_600_000_000, 6_600_000_000]]
    assert spec.reader("layout_s").read({"spans": rows}) == \
        pytest.approx(2.5)
    assert spec.reader("init_state_s").read({"spans": rows}) == \
        pytest.approx(4.0)
    assert spec.reader("layout_s").read({"spans": rows[1:]}) is None


PROFILE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 5000 duration_ps: 3000 }
    events { metadata_id: 8 offset_ps: 9000 duration_ps: 1000
             stats { metadata_id: 1 ref_value: 3 } }
    events { metadata_id: 9 offset_ps: 19000 duration_ps: 1000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 5000 duration_ps: 3000 } }
  event_metadata { key: 7 value { id: 7 name: "fusion.3"
    stats { metadata_id: 1 str_value: "jit(step)/admm_w/dot_general" } } }
  event_metadata { key: 8 value { id: 8 name: "while.1" } }
  event_metadata { key: 9 value { id: 9 name: "copy.1" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(step)/admm_z/while" } }
}
planes { id: 2 name: "Task Environment"
  stats { metadata_id: 1 uint64_value: 123456789 }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } } }
'''


def test_xspace_reads_scope_paths_from_event_and_metadata_stats(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(PROFILE))
    space = xspace.read(str(path))
    assert xspace.scoped(space) == {"0": [
        ["jit(step)/admm_w/dot_general", 1005.0, 3.0],
        ["jit(step)/admm_z/while", 1009.0, 1.0]]}
    assert xspace.profile_start_ns(space) == 123456789
    # the same times as the profiler's own reader gives trace.load
    pd = ProfileData.from_file(str(path))
    ops = [e for p in pd.planes for line in p.lines if line.name == "XLA Ops"
           for e in line.events]
    assert [e.start_ns for e in ops[:2]] == [1005.0, 1009.0]
