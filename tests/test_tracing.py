"""The program's own tracing: device scopes on the ADMM sub-updates, host
spans in the constructor and ``train()``, and the line-search counters in
``ParallelState.probes``.

The counters are checked against counts made outside the program: the
number of times a wrapped objective runs (``jax.debug.callback``), a plain
Python loop over the same backtracking test, and the step sizes the
searches leave behind (with ``backtrack_growth`` 2, τ_out / τ_start is
2^iterations exactly).
"""
import contextlib
import glob
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import gcn, graph
from repro.core.parallel import (AXIS, ParallelADMMTrainer, TrainerConfig,
                                 backtracking_step_lanes,
                                 backtracking_step_psum, fista_lanes,
                                 probe_columns)
from repro.core.subproblems import ADMMConfig
from repro.util import spans

SCOPES = ("admm_w", "admm_z", "admm_fista", "admm_dual")
TRAIN_SPANS = ("train.step", "train.wait", "train.eval", "train.sync")


def _graph(m=4):
    return graph.synthetic_powerlaw_communities(
        num_parts=m, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.8)


def _trainer(g=None, part=None, mesh=None, hidden=(8,), **admm_kw):
    if g is None:
        g, part = _graph()
    if mesh is None:
        mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    cfg = gcn.GCNConfig(layer_dims=(8, *hidden, g.num_classes))
    admm = ADMMConfig(**{"nu": 1e-3, "rho": 1e-3, **admm_kw})
    return ParallelADMMTrainer(cfg, admm, g, num_parts=int(part.max()) + 1,
                               seed=0, part=part, mesh=mesh,
                               config=TrainerConfig.packed())


def _start(x, admm):
    """Where a search starts from its warm value: one growth step back."""
    return np.maximum(np.asarray(x) / admm.backtrack_growth, 1e-8)


def _iters(start, end):
    return np.rint(np.log2(np.asarray(end) / start)).astype(int)


def _counts_from_step_sizes(before, after, admm, n_shards):
    """The ``probe_columns`` per shard of the W and hidden-Z searches of
    one round, read off τ and θ: [evaluations, capped] summed, then per
    search (FISTA keeps no step size in the state; the tests that use
    this run with ``fista_iters=0``, which leaves its columns at 0)."""
    n_l = len(before.taus)
    counts = np.zeros((n_shards, len(probe_columns(n_l))), int)
    for l, (t0, t1) in enumerate(zip(before.taus, after.taus)):
        it = int(_iters(_start(t0, admm), t1))
        col = 2 + 2 * l
        counts[:, col:col + 2] += [it + 1, it >= admm.max_backtracks]
    for l, (th0, th1) in enumerate(zip(before.thetas[:-1],
                                       after.thetas[:-1])):
        lane_it = _iters(_start(th0, admm), th1).reshape(n_shards, -1)
        it = lane_it.max(axis=1)          # the loop runs until every lane
        col = 2 + 2 * (n_l + l)           # of the shard has accepted
        counts[:, col] += it + 1
        counts[:, col + 1] += it >= admm.max_backtracks
    counts[:, 0] = counts[:, 2::2].sum(axis=1)
    counts[:, 1] = counts[:, 3::2].sum(axis=1)
    return counts


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def step_hlo():
    t = _trainer()
    return t._step.lower(*t._analysis_args).compile().as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_step_carries_the_sub_update_scope(step_hlo, scope):
    paths = re.findall(r'op_name="([^"]*)"', step_hlo)
    assert any(f"/{scope}/" in p for p in paths), scope


@pytest.mark.parametrize("scope", ("admm_w", "admm_z", "admm_fista"))
def test_line_search_loops_sit_inside_their_scope(step_hlo, scope):
    paths = re.findall(r'op_name="([^"]*)"', step_hlo)
    assert any(re.search(rf"/{scope}/.*while", p) for p in paths), scope


LAYER_SCOPES = ("admm_w/l1", "admm_w/l2", "admm_w/l3", "admm_z/l1",
                "admm_z/l2")


@pytest.fixture(scope="module")
def step_hlo_three_layers():
    t = _trainer(hidden=(8, 8))
    return t._step.lower(*t._analysis_args).compile().as_text()


@pytest.mark.parametrize("scope", LAYER_SCOPES)
def test_three_layer_step_carries_a_scope_per_layer(step_hlo_three_layers,
                                                    scope):
    paths = re.findall(r'op_name="([^"]*)"', step_hlo_three_layers)
    assert any(f"/{scope}/" in p for p in paths), scope
    assert not any(f"/{s}/" in p for p in paths
                   for s in ("admm_w/l4", "admm_z/l3", "admm_fista/l"))


def test_layer_scopes_leave_the_iterates_bitwise(monkeypatch):
    """A 2-layer round traced with the per-layer scopes is bitwise the
    round traced with the sub-update scopes alone."""
    with_layers, without = _trainer(), _trainer()
    with_layers.step()
    named_scope = jax.named_scope

    def outer_only(name):
        if re.fullmatch(r"l\d+", name):
            return contextlib.nullcontext()
        return named_scope(name)
    monkeypatch.setattr(jax, "named_scope", outer_only)
    hlo = without._step.lower(*without._analysis_args).compile().as_text()
    without.step()
    monkeypatch.undo()
    assert "/admm_w/l1/" not in hlo and "/admm_w/" in hlo
    for _ in range(2):
        with_layers.step()
        without.step()
    a, b = jax.device_get(with_layers.state), jax.device_get(without.state)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# counters of single searches, against a count made outside the program
# ---------------------------------------------------------------------------

def _callback_counter():
    calls = []

    def tick():
        calls.append(1)
    return calls, lambda: jax.debug.callback(tick)


@pytest.mark.parametrize("tau0, cap", [(1.0, 30), (1e-3, 30), (1e-3, 4)])
def test_psum_search_counts_the_objective_evaluations(tau0, cap):
    admm = ADMMConfig(max_backtracks=cap)
    target = jnp.linspace(-2.0, 3.0, 12).reshape(3, 4)
    calls, tick = _callback_counter()

    def local_obj(w):
        tick()
        return 2.0 * jnp.sum((w - target) ** 2)

    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    run = jax.jit(jax.shard_map(
        lambda x, t: backtracking_step_psum(local_obj, x, t, admm),
        mesh=mesh, in_specs=(jax.P(), jax.P()), out_specs=jax.P(),
        check_vma=False))
    _, tau, probes = run(jnp.zeros((3, 4)), jnp.float32(tau0))
    jax.effects_barrier()
    # value_and_grad evaluates once more, before the search
    assert int(probes[0]) == len(calls) - 1
    it = int(_iters(_start(tau0, admm), tau))
    assert list(np.asarray(probes)) == [it + 1, int(it >= cap)]


@pytest.mark.parametrize("cap", [30, 2])
def test_lane_search_counts_the_objective_evaluations(cap):
    admm = ADMMConfig(max_backtracks=cap)
    x = jnp.ones((3, 5, 2))
    scale = jnp.asarray([1.0, 40.0, 900.0])   # lanes of unlike curvature
    calls, tick = _callback_counter()

    def obj_lanes(z):
        tick()
        return 0.5 * scale * jnp.sum(z * z, axis=(1, 2))

    theta0 = jnp.full((3,), 2.0)
    run = jax.jit(lambda z, t: backtracking_step_lanes(obj_lanes, z, t,
                                                       admm))
    _, theta, probes = run(x, theta0)
    jax.effects_barrier()
    # the objective and its gradient run once each before the search
    assert int(probes[0]) == len(calls) - 2
    it = int(_iters(_start(theta0, admm), theta).max())
    assert list(np.asarray(probes)) == [it + 1, int(it >= cap)]


def _fista_plain_count(admm, b, u, labels, mask, z, denom):
    """``fista_lanes`` as a Python loop, counting its Lipschitz tests."""
    def obj(z):
        logp = jax.nn.log_softmax(z, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        r = z - b
        return (jnp.sum(nll * mask, axis=1) / denom
                + jnp.sum(u * r, axis=(1, 2))
                + 0.5 * admm.rho * jnp.sum(r * r, axis=(1, 2)))

    grad = jax.grad(lambda z: obj(z).sum())
    y, t = z, 1.0
    lip = np.full(z.shape[0], admm.rho + 1.0, np.float32)
    evals = capped = 0
    for _ in range(admm.fista_iters):
        vals, g = obj(y), grad(y)
        g_sq = jnp.sum(g * g, axis=(1, 2))

        def accepted(lip):
            bound = vals - 0.5 * g_sq / lip
            tol = admm.backtrack_rtol * (jnp.abs(bound) + 1e-12)
            return np.asarray(obj(y - g / lip[:, None, None]) <= bound + tol)

        done, it = accepted(lip), 0
        evals += 1
        while not done.all() and it < admm.max_backtracks:
            lip = np.where(done, lip, lip * admm.backtrack_growth)
            done = done | accepted(lip)
            evals, it = evals + 1, it + 1
        capped += it >= admm.max_backtracks
        z_new = y - g / lip[:, None, None]
        t_new = 0.5 * (1.0 + math.sqrt(1.0 + 4.0 * t * t))
        y = z_new + ((t - 1.0) / t_new) * (z_new - z)
        z, t, lip = z_new, t_new, lip * 0.9
    return [evals, capped]


@pytest.mark.parametrize("cap", [30, 1])
def test_fista_sums_its_lipschitz_tests(cap):
    admm = ADMMConfig(rho=1e-3, max_backtracks=cap, fista_iters=5)
    rng = np.random.default_rng(0)
    k, n, c = 2, 6, 4
    b = jnp.asarray(rng.normal(size=(k, n, c)) * 3, jnp.float32)
    u = jnp.asarray(rng.normal(size=(k, n, c)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, c, size=(k, n)), jnp.int32)
    mask = jnp.ones((k, n), jnp.float32)
    z0 = jnp.zeros((k, n, c), jnp.float32)
    # a small denominator makes the loss term stiffer than the starting
    # Lipschitz guess, so the searches have to double it
    denom = 0.02
    _, probes = jax.jit(lambda *a: fista_lanes(admm, *a))(
        b, u, labels, mask, z0, jnp.float32(denom))
    expect = _fista_plain_count(admm, b, u, labels, mask, z0, denom)
    assert list(np.asarray(probes)) == expect
    assert expect[0] > admm.fista_iters          # some test was repeated
    assert (expect[1] > 0) == (cap == 1)


# ---------------------------------------------------------------------------
# counters of the trainer's rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tau_init, cap", [(1.0, 30), (1e-4, 3)])
def test_round_counters_match_the_step_sizes(tau_init, cap):
    t = _trainer(tau_init=tau_init, max_backtracks=cap, fista_iters=0)
    total = np.zeros((1, len(probe_columns(2))), int)
    for _ in range(3):
        before = jax.device_get(t.state)
        t.step()
        after = jax.device_get(t.state)
        total += _counts_from_step_sizes(before, after, t.admm, 1)
        np.testing.assert_array_equal(np.asarray(after.probes), total)
    assert np.asarray(t.state.probes).dtype == np.int32
    if cap == 3:
        assert total[0, 1] >= 3       # the first W search of each round


def test_three_layer_round_counters_match_the_step_sizes():
    t = _trainer(hidden=(8, 8), tau_init=1e-3, max_backtracks=30,
                 fista_iters=0)
    total = np.zeros((1, len(probe_columns(3))), int)
    for _ in range(3):
        before = jax.device_get(t.state)
        t.step()
        after = jax.device_get(t.state)
        total += _counts_from_step_sizes(before, after, t.admm, 1)
    np.testing.assert_array_equal(np.asarray(t.state.probes), total)


def test_per_search_columns_sum_to_the_totals():
    t = _trainer(hidden=(8, 8), tau_init=1e-4, max_backtracks=3)
    for _ in range(3):
        t.step()
    p = np.asarray(t.state.probes)
    cols = probe_columns(3)
    assert cols[:4] == ["evals", "capped", "w1.evals", "w1.capped"]
    assert p.shape == (1, len(cols)) == (1, 14)
    np.testing.assert_array_equal(p[:, 0], p[:, 2::2].sum(axis=1))
    np.testing.assert_array_equal(p[:, 1], p[:, 3::2].sum(axis=1))
    assert p[0, 1] > 0
    # every search runs at least its first test each round; FISTA runs
    # ``fista_iters`` of them
    evals = dict(zip(cols[2::2], p[0, 2::2]))
    assert all(v >= 3 for v in evals.values()), evals
    assert evals["z3.evals"] >= 3 * t.admm.fista_iters


def test_capped_searches_rise_round_by_round():
    t = _trainer(tau_init=1e-4, max_backtracks=2)
    seen = [0]
    for _ in range(3):
        t.step()
        seen.append(int(np.asarray(t.state.probes)[0, 1]))
    assert all(b > a for a, b in zip(seen, seen[1:])), seen
    searches = 2 + 1 + t.admm.fista_iters         # W, hidden Z, FISTA
    assert int(np.asarray(t.state.probes)[0, 0]) >= 3 * searches


def test_counters_change_no_iterate():
    """The count rides along in the state and feeds nothing back."""
    a, b = _trainer(), _trainer()
    offset = np.zeros(b.state.probes.shape, np.int32)
    offset[0, :2] = [10 ** 6, 7]
    b.state = b.state._replace(probes=jax.device_put(
        offset, b.state.probes.sharding))
    for _ in range(2):
        a.step()
        b.step()
    for x, y in zip(jax.tree.leaves(a.state)[:-1],
                    jax.tree.leaves(b.state)[:-1]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(
        np.asarray(b.state.probes) - np.asarray(a.state.probes), offset)


_SHARDED_WORKER = r"""
import jax, numpy as np
from jax.sharding import AxisType
import test_tracing as tt
from repro.core.parallel import AXIS
g, part = tt._graph(m=8)
mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,))
t = tt._trainer(g, part, mesh, tau_init=1e-2, max_backtracks=30,
                fista_iters=0)
assert t.state.probes.shape == (4, 10)
total = np.zeros((4, 10), int)
for _ in range(3):
    before = jax.device_get(t.state)
    t.step()
    after = jax.device_get(t.state)
    total += tt._counts_from_step_sizes(before, after, t.admm, 4)
np.testing.assert_array_equal(np.asarray(t.state.probes), total)
print("SHARDED_COUNTS_OK", total.tolist())
"""


def test_counters_are_per_shard_on_4_devices():
    """Each shard counts its own lane searches; the psum-ed W search adds
    the same count on every shard."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here])
    out = subprocess.run([sys.executable, "-c", _SHARDED_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_COUNTS_OK" in out.stdout, out.stdout


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def test_constructor_records_layout_then_initial_state():
    g, part = _graph()
    with spans.recording() as rec:
        _trainer(g, part)
    assert [s.name for s in rec] == ["construct.layout",
                                     "construct.init_state",
                                     "construct.input_agg"]
    assert all(s.parent is None and s.end_ns >= s.start_ns for s in rec)
    assert rec[0].end_ns <= rec[1].start_ns


def test_train_records_each_round_under_its_caller():
    t = _trainer()
    with spans.recording() as rec:
        with spans.span("caller"):
            t.train(2)
    assert [s.name for s in rec] == list(TRAIN_SPANS) * 2 + ["caller"]
    assert all(s.parent == "caller" for s in rec[:-1])
    assert rec[-1].parent is None
    inner = rec[:-1]
    assert all(a.end_ns <= b.start_ns for a, b in zip(inner, inner[1:]))


def test_nothing_is_recorded_outside_recording():
    t = _trainer()
    assert isinstance(spans.span("x"), jax.profiler.TraceAnnotation)
    t.train(1)
    with spans.recording() as rec:
        pass
    assert rec == []
    with spans.recording() as rec:
        t.train(1)
    t.train(1)
    assert len(rec) == len(TRAIN_SPANS)


def test_recorded_span_matches_its_profiler_event(tmp_path):
    """The in-memory copy and the profiler's host event are one span on
    one clock: they agree to 0.1 ms once the trace's start is added."""
    from jax.profiler import ProfileData
    t = _trainer()
    t.train(1)
    jax.profiler.start_trace(str(tmp_path))
    with spans.recording() as rec:
        t.train(2)
    jax.profiler.stop_trace()
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(pb[0])
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    events = sorted(((e.name, e.start_ns, e.end_ns) for p in pd.planes
                     if p.name.startswith("/host:") for line in p.lines
                     for e in line.events if e.name in TRAIN_SPANS),
                    key=lambda e: e[1])
    assert [e[0] for e in events] == [s.name for s in rec]
    for (_, a, b), s in zip(events, rec):
        assert abs(start + a - s.start_ns) < 1e5
        assert abs(start + b - s.end_ns) < 1e5
