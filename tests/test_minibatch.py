"""Stochastic community minibatching: sampler, sub-plan, staleness, and
the sampled trainer itself.

The contract under test: sampling changes WHICH blocks step, never what a
stepped block computes.  ``batch_fraction=1.0`` must reproduce the
full-batch packed trainer bitwise (every minibatch knob is
exact-at-identity: masks of 1.0, decay 1.0, a full-set restricted plan is
the plan).  Under real sampling the restricted exchange carries only
messages into sampled shards, unsampled lanes hold their iterates
bit-for-bit, the staleness weight decays monotonically with age, and the
augmented Lagrangian still descends.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import gcn, graph, messages
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig, stale_weights
from repro.sharding.partition import CommunityBatchSampler


def _skewed(m=8, seed=0, skew=0.8):
    return graph.synthetic_powerlaw_communities(
        num_parts=m, nodes_per_part=12, attach=1, seed=seed, feat_dim=8,
        size_skew=skew)


def _trainer(g, part, mesh, config):
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    m = int(part.max()) + 1
    return ParallelADMMTrainer(cfg, admm, g, num_parts=m, seed=0,
                               part=part, mesh=mesh, config=config)


# ---------------------------------------------------------------------------
# the batch sampler
# ---------------------------------------------------------------------------

def test_sampler_is_seeded_and_deterministic():
    w = np.array([4.0, 1.0, 2.0, 1.0])
    a = CommunityBatchSampler(4, 0.5, seed=7, weights=w)
    b = CommunityBatchSampler(4, 0.5, seed=7, weights=w)
    assert [a.batch(t) for t in range(8)] == [b.batch(t) for t in range(8)]
    assert a.cycle(3) == b.cycle(3)
    # under uniform weights the seeded permutation decides the batch
    # composition — different seeds must eventually disagree
    u7 = CommunityBatchSampler(6, 0.5, seed=7)
    u8 = CommunityBatchSampler(6, 0.5, seed=8)
    assert any(u7.cycle(i) != u8.cycle(i) for i in range(16))


def test_sampler_covers_every_shard_once_per_cycle():
    s = CommunityBatchSampler(6, 1 / 3, seed=0)
    for c in range(4):
        seen = sorted(x for b in s.cycle(c) for x in b)
        assert seen == list(range(6))
    # batch(t) walks the cycles in order
    flat = [s.batch(t) for t in range(2 * s.num_batches)]
    assert flat[:s.num_batches] == list(s.cycle(0))
    assert flat[s.num_batches:] == list(s.cycle(1))


def test_sampler_balances_by_weight():
    # one dominant shard: the greedy must isolate it rather than pair it
    w = np.array([100.0, 1.0, 1.0, 1.0])
    s = CommunityBatchSampler(4, 0.5, seed=0, weights=w)
    batches = s.cycle(0)
    assert len(batches) == 2
    heavy = [b for b in batches if 0 in b][0]
    assert heavy == (0,)


def test_sampler_clamps_and_validates():
    # num_batches never exceeds n_shards (f -> 0) and f=1 is one batch
    assert CommunityBatchSampler(4, 0.01).num_batches == 4
    assert CommunityBatchSampler(4, 1.0).num_batches == 1
    assert CommunityBatchSampler(1, 0.25).num_batches == 1
    with pytest.raises(ValueError, match="batch_fraction"):
        CommunityBatchSampler(4, 0.0)
    with pytest.raises(ValueError, match="batch_fraction"):
        CommunityBatchSampler(4, 1.5)


# ---------------------------------------------------------------------------
# the restricted exchange plan
# ---------------------------------------------------------------------------

def _plan(n_shards=4):
    g, part = _skewed()
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    return messages.build_neighbor_exchange(
        layout.neighbor_mask, n_shards, layout.n_pad,
        sizes=layout.sizes, row_counts=layout.eff_row_counts())


def test_restrict_exchange_full_set_is_the_plan():
    plan = _plan()
    assert messages.restrict_exchange(plan, {0, 1, 2, 3}) is plan


def test_restrict_exchange_keeps_only_sampled_destinations():
    plan = _plan()
    for sampled in ({0}, {1, 3}, {0, 2}):
        sub = messages.restrict_exchange(plan, sampled)
        pairs = [p for r in sub.rounds for p in r.pairs]
        assert pairs, "restriction emptied a non-empty schedule"
        assert all(dst in sampled for _, dst in pairs)
        # unsampled sources still send into sampled shards
        full_into = {(s, d) for r in plan.rounds for (s, d) in r.pairs
                     if d in sampled}
        assert set(pairs) == full_into
        # geometry is untouched — localized ELL indices stay valid
        assert sub.r_pad == plan.r_pad
        assert sub.n_pad == plan.n_pad
        # wire shrinks
        full_w = messages.exchange_bytes(plan, [8])["wire_bytes"]
        sub_w = messages.exchange_bytes(sub, [8])["wire_bytes"]
        assert sub_w < full_w


def test_restrict_exchange_validates():
    plan = _plan()
    with pytest.raises(ValueError, match="non-empty"):
        messages.restrict_exchange(plan, set())
    with pytest.raises(ValueError, match="out of range"):
        messages.restrict_exchange(plan, {0, 7})


def _layout_and_plan(seed=0, skew=0.8, n_shards=4, packed=True):
    g, part = _skewed(seed=seed, skew=skew)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    plan = messages.build_neighbor_exchange(
        layout.neighbor_mask, n_shards, layout.n_pad,
        sizes=layout.sizes,
        row_counts=layout.eff_row_counts() if packed else None)
    return layout, plan


def test_restrict_exchange_geometry_fuzz():
    """Seeded randomized sweep (hypothesis-free) over graphs, shard
    counts, plan modes and sampled sets.  The load-bearing invariant is
    destination-additivity: every pair has exactly one destination, so
    the sub-plan's true rows (and needed bytes) must equal the sum over
    the singleton restrictions — round padding is the only non-additive
    quantity, and it only ever shrinks."""
    rng = np.random.default_rng(1234)
    for trial in range(8):
        seed = int(rng.integers(0, 100))
        skew = float(rng.uniform(0.0, 1.2))
        n_shards = int(rng.choice([2, 4, 8]))
        packed = bool(rng.integers(0, 2))
        _, plan = _layout_and_plan(seed=seed, skew=skew,
                                   n_shards=n_shards, packed=packed)
        full_pairs = {p for r in plan.rounds for p in r.pairs}
        full_eb = messages.exchange_bytes(plan, [8])
        singles = {d: messages.restrict_exchange(plan, {d})
                   for d in range(n_shards)}
        for _ in range(4):
            k = int(rng.integers(1, n_shards + 1))
            sampled = set(int(s) for s in
                          rng.choice(n_shards, size=k, replace=False))
            sub = messages.restrict_exchange(plan, sampled)
            # pairs are exactly the full set filtered by destination
            sub_pairs = {p for r in sub.rounds for p in r.pairs}
            assert sub_pairs == {p for p in full_pairs
                                 if p[1] in sampled}, (trial, sampled)
            # geometry untouched: localized indices stay valid
            assert sub.r_pad == plan.r_pad
            assert sub.n_pad == plan.n_pad
            assert sub.needed_ids == plan.needed_ids
            assert sub.row_counts == plan.row_counts
            assert sub.plane_rows == plan.plane_rows
            assert sub.recv_plane_rows == plan.recv_plane_rows
            # rounds only shrink: pad rows bounded by the source round,
            # slot tables trimmed to the surviving pad width
            by_off = {r.offset: r for r in plan.rounds}
            for r in sub.rounds:
                src = by_off[r.offset]
                assert 0 < r.rows_pad <= src.rows_pad
                assert r.send_idx.shape[1] == r.rows_pad
                assert r.recv_slot.shape[1] == r.rows_pad
            # destination-additivity of the true (padding-free) rows
            eb = messages.exchange_bytes(sub, [8])
            assert eb["true_rows"] == sum(
                messages.exchange_bytes(singles[d], [8])["true_rows"]
                for d in sampled), (trial, sampled)
            assert eb["wire_bytes"] == \
                eb["p2p_needed_bytes"] + eb["padding_bytes"]
            assert eb["wire_bytes"] <= full_eb["wire_bytes"]
            # arrival groups of the sub-schedule stay in range
            arr = messages.arrival_rounds(sub)
            assert arr.min() >= -1
            assert arr.max() < max(sub.num_rounds, 1)


def test_overlap_stats_price_the_restricted_plan():
    """`overlap_stats` on a restricted sub-plan must price exactly that
    sub-plan's scheduled wire: `total_wire_bytes` equals
    `exchange_bytes(sub)["wire_bytes"]` for any payload widths, and the
    exposed share never exceeds the total."""
    layout, plan = _layout_and_plan()
    nbr = layout.neighbor_mask
    for sampled in ({0}, {1, 3}, {0, 2, 3}, {0, 1, 2, 3}):
        sub = messages.restrict_exchange(plan, sampled)
        for cs in ([8], [8, 8, 4], [8, 8, 4, 4, 8, 4, 8]):
            ov = messages.overlap_stats(sub, nbr, cs, enabled=True)
            eb = messages.exchange_bytes(sub, cs)
            assert ov["total_wire_bytes"] == eb["wire_bytes"]
            assert ov["exposed_wire_bytes"] <= ov["total_wire_bytes"]
            assert -1e-9 <= ov["exposed_wire_s"] \
                <= ov["total_wire_s"] + 1e-9
            assert 0.0 <= ov["overlap_efficiency"] <= 1.0
            assert ov["num_groups"] == sub.num_rounds + 1
        # a strict restriction prices strictly less wire than the plan
        if len(sampled) < plan.n_shards:
            full = messages.overlap_stats(plan, nbr, [8], enabled=True)
            rst = messages.overlap_stats(sub, nbr, [8], enabled=True)
            assert rst["total_wire_bytes"] < full["total_wire_bytes"]


# ---------------------------------------------------------------------------
# the staleness weight
# ---------------------------------------------------------------------------

def test_stale_weights_monotone_and_exact_at_zero():
    ages = np.array([0, 1, 2, 5, 10])
    d = np.asarray(stale_weights(ages, 0.5))
    # exactly 1.0 at age 0 — the bitwise f=1.0 parity rests on this
    assert d[0] == np.float32(1.0)
    assert np.all(np.diff(d) < 0)                 # strictly decaying
    np.testing.assert_allclose(d, 0.5 ** ages.astype(np.float32),
                               rtol=1e-6)
    # decay 1.0 disables damping entirely (exact block-coordinate steps)
    np.testing.assert_array_equal(np.asarray(stale_weights(ages, 1.0)),
                                  np.ones(5, np.float32))


# ---------------------------------------------------------------------------
# the sampled trainer, one shard (multi-shard runs in the subprocess)
# ---------------------------------------------------------------------------

def test_fraction_one_matches_packed_bitwise_one_shard():
    """f=1.0 samples every shard every round: W/Z/U and the Lagrangian
    must equal the full-batch packed trainer BITWISE (identity masks and
    decay 1.0 multiply exactly, the full-set sub-plan IS the plan)."""
    g, part = _skewed()
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    ref = _trainer(g, part, mesh, TrainerConfig.packed())
    mb = _trainer(g, part, mesh,
                  TrainerConfig.minibatch(batch_fraction=1.0))
    for _ in range(4):
        ref.step()
        mb.step()
    for zr, zm in zip(ref.state.zs, mb.state.zs):
        np.testing.assert_array_equal(np.asarray(zr), np.asarray(zm))
    np.testing.assert_array_equal(np.asarray(ref.state.u),
                                  np.asarray(mb.state.u))
    for wr, wm in zip(ref.state.weights, mb.state.weights):
        np.testing.assert_array_equal(np.asarray(wr), np.asarray(wm))
    assert float(ref._lagrangian(ref.state)) == \
        float(mb._lagrangian(mb.state))


def test_minibatch_comm_stats_and_age_tracking():
    g, part = _skewed()
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    mb = _trainer(g, part, mesh,
                  TrainerConfig.minibatch(batch_fraction=1.0,
                                          stale_decay=0.75,
                                          sample_seed=3))
    st = mb.comm_stats["minibatch"]
    assert st["enabled"] is True
    assert st["batch_fraction"] == 1.0
    assert st["stale_decay"] == 0.75
    assert st["sample_seed"] == 3
    assert st["num_batches"] == 1                 # one shard -> full batch
    assert st["sampled_state_rows"] == st["full_state_rows"]
    mb.step()
    assert mb.comm_stats["minibatch"]["rounds"] == 1
    # every community sampled every round -> ages pinned at zero
    assert mb.comm_stats["minibatch"]["max_age"] == 0
    assert np.all(mb._ages == 0)
    # the full-batch trainer reports the disabled stub
    full = _trainer(g, part, mesh, TrainerConfig.packed())
    assert full.comm_stats["minibatch"] == {"enabled": False}


# ---------------------------------------------------------------------------
# 4-shard subprocess: bitwise f=1.0, sampled wire < full, Lagrangian
# descent within the gap, and the analysis proof on the sampled step
# ---------------------------------------------------------------------------

_MB_WORKER = r"""
import numpy as np, jax
from repro import analysis
from repro.core import gcn, graph, messages
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig
from jax.sharding import AxisType

g, part = graph.synthetic_powerlaw_communities(
    num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
    size_skew=0.8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:4])

def build(config):
    return ParallelADMMTrainer(cfg, admm, g, num_parts=8, seed=0,
                               part=part, mesh=mesh, config=config)

# --- f=1.0 bitwise parity on 4 shards ---
ref = build(TrainerConfig.packed())
mb1 = build(TrainerConfig.minibatch(batch_fraction=1.0))
for _ in range(3):
    ref.step(); mb1.step()
for zr, zm in zip(ref.state.zs, mb1.state.zs):
    np.testing.assert_array_equal(np.asarray(zr), np.asarray(zm))
np.testing.assert_array_equal(np.asarray(ref.state.u),
                              np.asarray(mb1.state.u))
for wr, wm in zip(ref.state.weights, mb1.state.weights):
    np.testing.assert_array_equal(np.asarray(wr), np.asarray(wm))
assert float(ref._lagrangian(ref.state)) == float(mb1._lagrangian(mb1.state))
print("MB_BITWISE_OK")

# --- sampled run: wire drops, Lagrangian descends within the gap ---
mb = build(TrainerConfig.minibatch(batch_fraction=0.5))
st = mb.comm_stats["minibatch"]
assert st["enabled"] and st["num_batches"] == 2
assert st["sampled_wire_bytes"] < st["full_wire_bytes"]
assert st["mean_sampled_wire_bytes"] < st["full_wire_bytes"]
seen = sorted(s for b in st["schedule"] for s in b)
assert seen == [0, 1, 2, 3], st["schedule"]
lag0 = float(mb._lagrangian(mb.state))
for _ in range(8):
    mb.step()
lag = float(mb._lagrangian(mb.state))
assert lag < lag0, (lag0, lag)
lag_full = float(ref._lagrangian(ref.state))
for _ in range(5):
    ref.step()
lag_full = float(ref._lagrangian(ref.state))
# pinned gap: the sampled Lagrangian lands within 50% of full batch
# after the same 8 rounds (the benchmark pins 25% at M=32)
assert lag <= lag_full + 0.5 * abs(lag_full), (lag, lag_full)
# unsampled lanes aged, resampled lanes reset
assert mb._ages.max() >= 0 and mb._round == 8
assert len(mb._mb_steps) == 2          # one program per distinct batch
print("MB_SAMPLED_OK")

# --- the compiled sampled step's collectives are exactly the sub-plan ---
sampled = set(mb._sampler.batch(mb._round - 1))
sub_pairs = {p for r in mb._active_plan.rounds for p in r.pairs}
full_pairs = {p for r in mb._plan.rounds for p in r.pairs}
assert sub_pairs < full_pairs
assert all(d in sampled for _, d in sub_pairs)
rep = analysis.analyze_trainer(mb, config="p2p_minibatch")
assert analysis.no_findings(rep, rule="collective/permute-schedule")
assert analysis.no_findings(rep, rule="collective/no-allgather-under-p2p")
assert not rep.errors(), rep.summary()
print("MB_ANALYSIS_OK")
"""


def test_minibatch_on_4_shards():
    """The acceptance run: f=1.0 bitwise-matches full batch on 4 shards;
    f=0.5 wires strictly less per sampled round, descends the Lagrangian
    to within the pinned gap, and its compiled step's ppermute schedule
    is exactly the restricted sub-plan (no unsampled pair touched)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _MB_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("MB_BITWISE_OK", "MB_SAMPLED_OK", "MB_ANALYSIS_OK"):
        assert tag in out.stdout, out.stdout


# ---------------------------------------------------------------------------
# 4-shard subprocess: overlap composes with sampling — per-sub-plan
# arrival groups, tolerance parity, and per-step overlap re-pricing
# ---------------------------------------------------------------------------

_OV_WORKER = r"""
import numpy as np, jax
import jax.numpy as jnp
from repro.core import gcn, graph, messages
from repro.core.parallel import (AXIS, ParallelADMMTrainer, TrainerConfig,
                                 gathered_widths)
from repro.core.subproblems import ADMMConfig
from jax.sharding import AxisType

g, part = graph.synthetic_powerlaw_communities(
    num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
    size_skew=0.8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:4])

def build(config):
    return ParallelADMMTrainer(cfg, admm, g, num_parts=8, seed=0,
                               part=part, mesh=mesh, config=config)

# --- overlap=True now composes with batch_fraction < 1 ---
mb = build(TrainerConfig.minibatch(batch_fraction=0.5))
ov = build(TrainerConfig.minibatch(batch_fraction=0.5, overlap=True))
lag0 = float(ov._lagrangian(ov.state))
for _ in range(8):
    mb.step(); ov.step()
lag = float(ov._lagrangian(ov.state))
assert lag < lag0, (lag0, lag)

# same sample_seed -> same schedule; overlap only regroups the neighbour
# sum per arrival round, so the trajectories agree to summation-order
# tolerance
def delta(a, b):
    return max(
        max(float(jnp.max(jnp.abs(x - y)))
            for x, y in zip(a.weights, b.weights)),
        max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a.zs, b.zs)),
        float(jnp.max(jnp.abs(a.u - b.u))))
d = delta(mb.state, ov.state)
assert d <= 1e-4, f"overlap x minibatch parity {d}"
print("OV_MB_PARITY_OK")

# --- comm_stats["overlap"] prices the ACTIVE restricted plan ---
st = ov.comm_stats["overlap"]
assert st["enabled"] is True
sub = ov._active_plan
sub_pairs = {p for r in sub.rounds for p in r.pairs}
full_pairs = {p for r in ov._plan.rounds for p in r.pairs}
assert sub_pairs < full_pairs          # a strict sub-schedule is active
eb = messages.exchange_bytes(sub, gathered_widths(ov.cfg.layer_dims))
assert st["total_wire_bytes"] == eb["wire_bytes"], (st, eb)
assert st["exposed_wire_bytes"] <= st["total_wire_bytes"]
assert st["num_groups"] == sub.num_rounds + 1
print("OV_MB_PRICED_OK")
"""


def test_overlap_composes_with_minibatch_on_4_shards():
    """overlap=True + batch_fraction=0.5 trains (Lagrangian descends),
    stays within summation-order tolerance of the non-overlap sampled
    trainer, and `comm_stats["overlap"]` re-prices the active restricted
    sub-plan — its total equals that sub-plan's `exchange_bytes` wire,
    with the exposed share bounded by it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _OV_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("OV_MB_PARITY_OK", "OV_MB_PRICED_OK"):
        assert tag in out.stdout, out.stdout
