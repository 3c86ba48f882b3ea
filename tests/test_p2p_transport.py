"""Neighbour-only ppermute transport: round-schedule correctness, wire-byte
invariants, and p2p vs allgather trainer parity on a real 2-shard mesh.

The schedule is host-side static (messages.NeighborExchange); the parity
test runs in a subprocess so XLA can be launched with 2 host devices, and
additionally proves from the compiled HLO that the p2p step contains no
all-gather op (no (M, n_pad, C) payload is ever materialised) while moving
fewer collective bytes than the allgather oracle.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import graph, messages
from repro.sharding.partition import ring_round_coloring


@pytest.fixture(scope="module", params=[(2, False), (4, False),
                                        (2, True), (4, True)])
def plan_case(request):
    """Whole-block plans on the uniform graph and row-exact plans on a
    size-skewed bucketed layout — the schedule tests hold for both."""
    n_shards, row_exact = request.param
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=8, nodes_per_part=12, attach=2, seed=4, feat_dim=8,
        size_skew=0.8 if row_exact else 0.0)
    layout = graph.build_community_layout(
        g.num_nodes, g.edges, part, compressed=True,
        pad_mode="bucketed" if row_exact else "global")
    plan = messages.build_neighbor_exchange(
        layout.neighbor_mask, n_shards, layout.n_pad,
        sizes=layout.sizes if row_exact else None)
    assert plan.row_exact == row_exact
    return layout, plan, n_shards


def _deliveries(plan):
    """(dst_shard, global_id, slot) triples the schedule transmits.

    Rows travel at node granularity: for every delivered community the
    helper additionally asserts that exactly its wired rows (true size on
    row-exact plans, all n_pad otherwise) arrive, each at the receive-
    buffer row its sender packed it for."""
    k, n = plan.lanes_per_shard, plan.n_pad
    rows_seen: dict[tuple, set] = {}
    for rnd in plan.rounds:
        for src, dst in rnd.pairs:
            for t in range(rnd.rows_pad):
                flat = int(rnd.recv_slot[dst, t])
                if flat >= plan.r_pad * n:   # round padding, dropped
                    continue
                slot, row = divmod(flat, n)
                lane, srow = divmod(int(rnd.send_idx[src, t]), n)
                assert srow == row, "send row misaligned with receive row"
                key = (dst, src * k + lane, slot)
                dup = rows_seen.setdefault(key, set())
                assert row not in dup, f"row {row} delivered twice: {key}"
                dup.add(row)
    out = []
    for (dst, gid, slot), rows in rows_seen.items():
        assert rows == set(range(plan.sizes[gid])), \
            f"community {gid} wired rows {sorted(rows)} != its true size"
        out.append((dst, gid, slot))
    return out


def test_schedule_covers_every_ell_edge_exactly_once(plan_case):
    """Every cross-shard ELL neighbour edge is delivered exactly once, to
    the slot the localized indices read; same-shard edges never hit the
    wire."""
    layout, plan, n_shards = plan_case
    csr = layout.compress()
    k = plan.lanes_per_shard
    deliveries = _deliveries(plan)
    seen = {}
    for dst, gid, slot in deliveries:
        assert (dst, gid) not in seen, f"duplicate delivery {(dst, gid)}"
        seen[(dst, gid)] = slot
        assert gid // k != dst, "own-shard rows must not be wired"
        # delivered to the slot the receive buffer maps this id to
        assert plan.slot_of(dst)[gid] == slot

    # required = every masked ELL edge, lifted to (shard, source community)
    required = set()
    for m in range(layout.num_parts):
        for d in np.flatnonzero(np.asarray(csr.ell_mask[m]) > 0):
            r = int(csr.ell_indices[m, d])
            if r // k != m // k:
                required.add((m // k, r))
            else:
                # resident rows are served locally from own_slots
                assert r in plan.needed_ids[m // k]
    assert set(seen) == required

    # localized indices stay inside the receive buffer and invert correctly
    local = plan.localize_indices(csr.ell_indices, csr.ell_mask)
    assert local.max() < plan.r_pad
    for m in range(layout.num_parts):
        ids = plan.needed_ids[m // k]
        for d in np.flatnonzero(np.asarray(csr.ell_mask[m]) > 0):
            assert ids[local[m, d]] == int(csr.ell_indices[m, d])


def test_rounds_are_partial_permutations(plan_case):
    """Each round is a partial permutation (the lax.ppermute contract).
    The colour index carries no ring-offset meaning anymore — the edge
    colouring packs messages of different offsets into one round."""
    _, plan, n_shards = plan_case
    for rnd in plan.rounds:
        srcs = [s for s, _ in rnd.pairs]
        dsts = [d for _, d in rnd.pairs]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)


def test_ring_round_coloring_rejects_bad_input():
    with pytest.raises(ValueError):
        ring_round_coloring([(0, 0)], 2)
    with pytest.raises(ValueError):
        ring_round_coloring([(0, 3)], 2)
    rounds = ring_round_coloring([(0, 1), (1, 0), (0, 2)], 4)
    # Δ = max degree = 2 (node 0 sends twice): exactly 2 colours, packed
    # contiguously from 0 — the historic ring-offset grouping burned a
    # round per distinct (dst-src) offset (here {1, 2, 3})
    assert rounds == {0: [(0, 1), (1, 0)], 1: [(0, 2)]}


def test_edge_coloring_is_degree_optimal():
    """König: the schedule always uses exactly Δ = max(out-degree,
    in-degree) rounds — the information-theoretic floor, since a shard can
    send (receive) at most one message per ppermute round."""
    rng = np.random.default_rng(7)
    for _ in range(100):
        n = int(rng.integers(2, 12))
        cand = [(u, v) for u in range(n) for v in range(n) if u != v]
        take = rng.random(len(cand)) < rng.uniform(0.1, 0.9)
        edges = [e for e, t in zip(cand, take) if t]
        if not edges:
            continue
        rounds = ring_round_coloring(edges, n)
        out_deg = np.zeros(n, int)
        in_deg = np.zeros(n, int)
        for u, v in edges:
            out_deg[u] += 1
            in_deg[v] += 1
        delta = max(out_deg.max(), in_deg.max())
        assert sorted(rounds) == list(range(len(rounds)))
        assert len(rounds) == delta
        assert sorted(e for grp in rounds.values() for e in grp) \
            == sorted(edges)
        for grp in rounds.values():
            assert len({u for u, _ in grp}) == len(grp)
            assert len({v for _, v in grp}) == len(grp)


def test_coloring_beats_ring_offsets_on_m32_powerlaw():
    """The round count the colouring buys on the benchmark topology: at
    M=32 communities over 16 shards (k=2) on the skewed power-law graph,
    the shard message graph has Δ = 7 but 15 distinct ring offsets — the
    offset grouping would burn 15 ppermute rounds where 7 suffice."""
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=32, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.9)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True)
    n_shards, k = 16, 2
    needed, _ = graph.shard_neighbor_graph(
        np.asarray(layout.neighbor_mask, bool), n_shards)
    edges = sorted({(int(r) // k, s) for s in range(n_shards)
                    for r in needed[s] if int(r) // k != s})
    rounds = ring_round_coloring(edges, n_shards)
    ring_offsets = len({(v - u) % n_shards for u, v in edges})
    out_deg = np.bincount([u for u, _ in edges], minlength=n_shards)
    in_deg = np.bincount([v for _, v in edges], minlength=n_shards)
    delta = int(max(out_deg.max(), in_deg.max()))
    assert len(rounds) == delta == 7
    assert ring_offsets == 15
    assert len(rounds) < ring_offsets


def test_wire_byte_invariant(plan_case):
    """p2p wire_bytes ≤ full_bytes, == true rows + round padding, and the
    scheduled true rows never exceed the mask-derived needed volume."""
    layout, plan, n_shards = plan_case
    dims = [16, 8]
    stats = messages.gather_bytes(layout.neighbor_mask, layout.n_pad, dims)
    stats.update(messages.exchange_bytes(plan, dims))
    messages.verify_transport_bytes(stats)      # must not raise
    assert stats["wire_bytes"] <= stats["full_bytes"]
    assert stats["wire_bytes"] == (stats["p2p_needed_bytes"]
                                   + stats["padding_bytes"])
    # the scheduled true rows never exceed the mask-derived need, and the
    # padding-included bound is recorded (hard only for whole-block plans)
    assert stats["p2p_needed_bytes"] <= stats["needed_bytes"]
    assert stats["wire_within_needed"] == \
        (stats["wire_bytes"] <= stats["needed_bytes"])
    if not plan.row_exact:
        assert stats["wire_within_needed"]
    else:
        # row-exact: strictly fewer true rows than the whole-block plan
        whole = messages.exchange_bytes(messages.build_neighbor_exchange(
            layout.neighbor_mask, n_shards, layout.n_pad), dims)
        assert stats["p2p_needed_bytes"] < whole["p2p_needed_bytes"]
        assert stats["wire_bytes"] < whole["wire_bytes"]
    assert stats["wire_bytes"] > 0              # cross-shard edges exist
    # the whole point: the schedule moves less than the all-gather
    assert stats["wire_bytes"] < stats["full_bytes"]

    bad = dict(stats)
    bad["padding_bytes"] += 1
    with pytest.raises(ValueError):
        messages.verify_transport_bytes(bad)
    bad = dict(stats)
    bad["wire_bytes"] = bad["full_bytes"] + 1
    with pytest.raises(ValueError):
        messages.verify_transport_bytes(bad)


@pytest.mark.parametrize("n_shards,k,trials", [
    (2, 1, 50), (4, 1, 50), (2, 2, 50), (2, 3, 50), (4, 2, 50), (4, 3, 50),
])
def test_wire_within_needed_fuzzed_topologies(n_shards, k, trials):
    """The ``wire_within_needed`` soft invariant, pinned down over fuzzed
    community topologies (300 total across the parametrization):

      * hard invariants never break: ``verify_transport_bytes`` passes,
        wire == true rows + round padding ≤ full, true rows ≤ needed;
      * the padding-included bound is soft EXACTLY when the round padding
        exceeds the mask slack — ``wire ≤ needed  ⟺  padding_bytes ≤
        needed_bytes − p2p_needed_bytes``, where the slack is the resident
        (own-lane) rows the masks count but the wire never carries plus
        per-shard deduplication of rows shared by co-hosted lanes;
      * at k=1 every scheduled row is a real row (``padding_bytes == 0``),
        so the bound can never be soft — the benchmark/CI regime.
    """
    m = n_shards * k
    rng = np.random.default_rng(1000 * n_shards + k)
    soft = 0
    for _ in range(trials):
        nbr = rng.random((m, m)) < rng.uniform(0.1, 0.9)
        nbr = nbr | nbr.T
        np.fill_diagonal(nbr, True)
        plan = messages.build_neighbor_exchange(nbr, n_shards, n_pad=8)
        stats = messages.gather_bytes(nbr, 8, [4])
        stats.update(messages.exchange_bytes(plan, [4]))
        out = messages.verify_transport_bytes(stats)   # hard: must not raise
        assert out["wire_bytes"] == (out["p2p_needed_bytes"]
                                     + out["padding_bytes"])
        assert out["wire_bytes"] <= out["full_bytes"]
        assert out["p2p_needed_bytes"] <= out["needed_bytes"]
        slack = out["needed_bytes"] - out["p2p_needed_bytes"]
        assert out["wire_within_needed"] == (out["padding_bytes"] <= slack)
        if k == 1:
            assert out["padding_bytes"] == 0 and out["wire_within_needed"]
        soft += not out["wire_within_needed"]
    if k == 1:
        assert soft == 0


def test_multilevel_wire_bytes_beat_bfs_kl_at_m32():
    """Partition quality IS wire volume: on the M=32 power-law benchmark
    graph the multilevel partition's NeighborExchange schedule moves no
    more bytes than the BFS+KL schedule (strictly fewer — its cut and ELL
    fan-in are strictly lower; benchmarks/check_bench.py guards the same
    inequality on the BENCH_speedup.json artifact in CI)."""
    g, _ = graph.synthetic_powerlaw_communities(
        32, nodes_per_part=32, attach=2, seed=0, feat_dim=8)
    wire = {}
    for method in ("bfs_kl", "multilevel"):
        part = graph.partition_graph(g.num_nodes, g.edges, 32, seed=0,
                                     method=method)
        layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                              compressed=True)
        plan = messages.build_neighbor_exchange(layout.neighbor_mask, 32,
                                                layout.n_pad)
        stats = messages.gather_bytes(layout.neighbor_mask, layout.n_pad,
                                      [64])
        stats.update(messages.exchange_bytes(plan, [64]))
        messages.verify_transport_bytes(stats)
        wire[method] = stats
    assert wire["multilevel"]["wire_bytes"] < wire["bfs_kl"]["wire_bytes"]
    assert (wire["multilevel"]["num_rounds"]
            <= wire["bfs_kl"]["num_rounds"])


def test_verify_transport_multi_lane_padding_is_soft():
    """On multi-lane shards round padding may exceed the mask slack on
    skewed topologies — that must be recorded (wire_within_needed=False),
    not raised, or legitimate compressed trainers become unconstructible.
    At k=1 padding is impossible by construction, so there it raises."""
    base = {"full_bytes": 1000, "needed_bytes": 500,
            "p2p_needed_bytes": 400, "padding_bytes": 200,
            "wire_bytes": 600, "lanes_per_shard": 2}
    out = messages.verify_transport_bytes(dict(base))
    assert out["wire_within_needed"] is False
    with pytest.raises(ValueError):
        messages.verify_transport_bytes(dict(base, lanes_per_shard=1))
    ok = messages.verify_transport_bytes(
        dict(base, padding_bytes=0, wire_bytes=400, lanes_per_shard=1))
    assert ok["wire_within_needed"] is True


def test_trainer_records_and_verifies_p2p_stats():
    from repro.core import gcn
    from repro.core.parallel import ParallelADMMTrainer
    from repro.core.subproblems import ADMMConfig

    g, part = graph.synthetic_powerlaw_communities(
        num_parts=4, nodes_per_part=16, attach=1, seed=2, feat_dim=8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    tr = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                             compressed=True)
    assert tr.transport == "p2p"
    assert tr.comm_stats["transport"] == "p2p"
    assert tr.comm_stats["wire_bytes"] <= tr.comm_stats["needed_bytes"]
    ag = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                             compressed=True, transport="allgather")
    assert ag.comm_stats["wire_bytes"] == ag.comm_stats["full_bytes"]
    with pytest.raises(ValueError):
        ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                            transport="p2p")            # dense + p2p
    with pytest.raises(ValueError):
        ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                            compressed=True, transport="carrier-pigeon")


_P2P_WORKER = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import gcn, graph, messages
from repro.core.parallel import AXIS, ParallelADMMTrainer
from repro.core.subproblems import ADMMConfig
from repro.launch import roofline
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

N_SHARDS = 4
assert len(jax.devices()) >= N_SHARDS, jax.devices()
g, part = graph.synthetic_powerlaw_communities(
    num_parts=12, nodes_per_part=12, attach=1, seed=0, feat_dim=8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh2 = jax.make_mesh((N_SHARDS,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:N_SHARDS])

# --- raw exchange == the needed rows of an all-gather, on real devices ---
layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                      compressed=True)
plan = messages.build_neighbor_exchange(layout.neighbor_mask, N_SHARDS,
                                        layout.n_pad)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(12, layout.n_pad, 8)).astype(np.float32))
ex = jax.shard_map(lambda v: messages.exchange_neighbors(plan, v, AXIS),
               mesh=mesh2, in_specs=(P(AXIS),), out_specs=P(AXIS),
               check_vma=False)
bufs = np.asarray(jax.jit(ex)(x)).reshape(N_SHARDS, plan.r_pad,
                                          layout.n_pad, 8)
for s in range(N_SHARDS):
    ids = plan.needed_ids[s]
    for slot, gid in enumerate(ids):
        np.testing.assert_allclose(bufs[s, slot], np.asarray(x[gid]),
                                   rtol=0, atol=0)
    # slots past the shard's needed set stay zero
    for slot in range(len(ids), plan.r_pad):
        assert np.abs(bufs[s, slot]).max() == 0.0
print("EXCHANGE_OK")

# --- trainer parity: p2p vs allgather, 3 iterations, W/Z/U + Lagrangian ---
p2p = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, part=part,
                          mesh=mesh2, compressed=True, transport="p2p")
ag = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, part=part,
                         mesh=mesh2, compressed=True, transport="allgather")
assert p2p.transport == "p2p" and ag.transport == "allgather"
for _ in range(3):
    p2p.step(); ag.step()
for za, zp in zip(ag.state.zs, p2p.state.zs):
    np.testing.assert_allclose(np.asarray(za), np.asarray(zp),
                               rtol=2e-4, atol=2e-5)
for wa, wp in zip(ag.state.weights, p2p.state.weights):
    np.testing.assert_allclose(np.asarray(wa), np.asarray(wp),
                               rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(np.asarray(ag.state.u), np.asarray(p2p.state.u),
                           rtol=2e-4, atol=2e-5)
lag_p, lag_a = float(p2p._lagrangian(p2p.state)), float(ag._lagrangian(ag.state))
assert abs(lag_p - lag_a) <= 1e-4 * max(1.0, abs(lag_a)), (lag_p, lag_a)
print("PARITY_OK")

# --- HLO proof via the analysis rules: the p2p step materialises no
#     gathered payload, its permute schedule matches the host plan, and
#     the full registry (memory, precision, donation) is clean ---
from repro import analysis
hlo_p2p = p2p._step.lower(p2p.state).compile().as_text()
hlo_ag = ag._step.lower(ag.state).compile().as_text()
rep = analysis.analyze_trainer(p2p, hlo_text=hlo_p2p, config="p2p-proof")
assert analysis.no_findings(rep, rule="collective/no-allgather-under-p2p")
assert analysis.no_findings(rep, rule="collective/permute-schedule")
assert analysis.no_findings(rep, rule="memory/no-dense-adjacency")
assert not rep.errors(), rep.summary()
rep_ag = analysis.analyze_trainer(ag, hlo_text=hlo_ag, config="ag-oracle")
assert not rep_ag.errors(), rep_ag.summary()
# deliberate break: the allgather program under the p2p expectations must
# trip exactly the rule that guards the transport contract
bad = analysis.analyze_hlo(
    hlo_ag, expectations=analysis.trainer_expectations(p2p))
assert bad.findings_for("collective/no-allgather-under-p2p"), \
    "linter missed the all-gather"
c_p2p = roofline.hlo_census(hlo_p2p).collective_bytes
c_ag = roofline.hlo_census(hlo_ag).collective_bytes
assert 0 < c_p2p < c_ag, (c_p2p, c_ag)
print(f"WIRE_OK p2p={c_p2p} allgather={c_ag}")

# --- bf16 wire path stays close ---
b16 = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, part=part,
                          mesh=mesh2, compressed=True, transport="p2p",
                          comm_bf16=True)
for _ in range(2):
    b16.step()
ref = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, part=part,
                          mesh=mesh2, compressed=True, transport="p2p")
for _ in range(2):
    ref.step()
for zb, zr in zip(b16.state.zs, ref.state.zs):
    np.testing.assert_allclose(np.asarray(zb), np.asarray(zr),
                               rtol=0.05, atol=0.05)
print("BF16_OK")
"""


_MULTILEVEL_WORKER = r"""
import jax
import numpy as np
from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer
from repro.core.serial import SerialADMMTrainer
from repro.core.subproblems import ADMMConfig
from jax.sharding import AxisType

N_SHARDS = 4
assert len(jax.devices()) >= N_SHARDS, jax.devices()
g, _ = graph.synthetic_powerlaw_communities(
    num_parts=12, nodes_per_part=12, attach=1, seed=0, feat_dim=8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh = jax.make_mesh((N_SHARDS,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:N_SHARDS])

serial = SerialADMMTrainer(cfg, admm, g, seed=0)
ml = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, mesh=mesh,
                         compressed=True, partitioner="multilevel")
ag = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, mesh=mesh,
                         compressed=True, partitioner="multilevel",
                         transport="allgather")
assert ml.partitioner == "multilevel" and ml.transport == "p2p"
assert ml.comm_stats["partitioner"] == "multilevel"
assert ml.comm_stats["partition"]["edge_cut"] == ml.partition_stats["edge_cut"]
for _ in range(3):
    serial.step(); ml.step(); ag.step()

# -- serial parity: the partitioner only reshapes communication; the math
#    is the global Algorithm 1 either way --
for zs_, zp in zip(serial.state.zs, ml.state.zs):
    np.testing.assert_allclose(np.asarray(zs_),
                               ml.layout.unpack(np.asarray(zp)),
                               rtol=2e-3, atol=2e-4)
for ws, wp in zip(serial.state.weights, ml.state.weights):
    np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                               rtol=2e-3, atol=2e-4)
np.testing.assert_allclose(np.asarray(serial.state.u),
                           ml.layout.unpack(np.asarray(ml.state.u)),
                           rtol=2e-3, atol=2e-4)
lag_s = float(serial._lagr(serial.a_tilde, serial.z0, serial.labels,
                           serial.train_mask, serial.state))
lag_m = float(ml._lagrangian(ml.state))
assert abs(lag_s - lag_m) <= 1e-4 * max(1.0, abs(lag_s)), (lag_s, lag_m)
print("SERIAL_PARITY_OK")

# -- transport parity under the multilevel partition: p2p vs allgather
#    bit-compare on the same layout --
for za, zp in zip(ag.state.zs, ml.state.zs):
    np.testing.assert_allclose(np.asarray(za), np.asarray(zp),
                               rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(np.asarray(ag.state.u), np.asarray(ml.state.u),
                           rtol=2e-4, atol=2e-5)
print("TRANSPORT_PARITY_OK")

# -- and the multilevel layout still compiles to a gather-free p2p step
#    (the analysis rules prove it, plus schedule/memory/precision) --
from repro import analysis
rep = analysis.analyze_trainer(ml, config="multilevel-p2p")
assert analysis.no_findings(rep, rule="collective/no-allgather-under-p2p")
assert analysis.no_findings(rep, rule="collective/permute-schedule")
assert not rep.errors(), rep.summary()
print("HLO_OK")
"""


def test_multilevel_partition_trainer_invariance():
    """ParallelADMMTrainer(partitioner='multilevel') on a real 4-shard mesh
    matches the serial trainer's W/Z/U and Lagrangian to float tolerance
    after 3 iterations, and its p2p step matches the allgather oracle on
    the same layout — the partitioner choice changes only who talks to
    whom, never the optimization semantics."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _MULTILEVEL_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("SERIAL_PARITY_OK", "TRANSPORT_PARITY_OK", "HLO_OK"):
        assert tag in out.stdout, out.stdout


def test_p2p_parity_on_multi_shard_mesh():
    """p2p vs allgather on a real 4-shard host mesh (subprocess: XLA locks
    the device count at first init): identical W/Z/U and Lagrangian after 3
    iterations, raw exchange delivers exactly the needed rows, and the
    compiled p2p HLO contains collective-permutes but no all-gather while
    moving fewer collective bytes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _P2P_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("EXCHANGE_OK", "PARITY_OK", "WIRE_OK", "BF16_OK"):
        assert tag in out.stdout, out.stdout
