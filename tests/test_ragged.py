"""Ragged (size-aware) community padding: bucket scheme, blockify round
trips, pad accounting, row-exact exchange, the ragged-vs-global trainer
A/B, and the bf16 ELL block store.

The invariant under test everywhere: bucketed padding and row-exact wire
change what is PROCESSED and TRANSMITTED, never the math — trainers under
any pad scheme produce identical iterates, while ``comm_stats`` shows
pad_bytes/pad_flops/wire_bytes dropping.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gcn, graph, messages
from repro.core.parallel import ParallelADMMTrainer
from repro.core.subproblems import ADMMConfig


# ---------------------------------------------------------------------------
# bucket scheme
# ---------------------------------------------------------------------------

def test_pad_ladder_is_geometric_and_8_aligned():
    ladder = graph.pad_ladder(512)
    assert ladder[0] == 8
    assert all(v % 8 == 0 for v in ladder)
    ratios = [b / a for a, b in zip(ladder, ladder[1:])]
    assert max(ratios) <= 2.0 and min(ratios) > 1.0
    # the power-of-two-ish prefix is exactly the documented one
    assert ladder[:8] == [8, 16, 24, 32, 48, 64, 96, 128]


def test_bucket_pad_sizes_cases():
    sizes = [0, 1, 7, 8, 9, 24, 25, 33, 100, 200]
    out = graph.bucket_pad_sizes(sizes, n_pad=200)
    assert out.tolist() == [0, 8, 8, 8, 16, 24, 32, 48, 128, 200]
    # every nonempty community fits its bucket; buckets never exceed n_pad
    assert all(b >= s for s, b in zip(sizes, out) if b)
    assert out.max() <= 200
    # cap at n_pad: a size in the top bucket keeps the global pad
    assert graph.bucket_pad_sizes([40], n_pad=40).tolist() == [40]


@pytest.mark.parametrize("m,n_c,skew", [
    (100, 1, 2.0), (200, 1, 3.0), (32, 32, 1.0), (8, 2, 5.0),
])
def test_size_skew_extreme_params_keep_contract(m, n_c, skew):
    """The remainder correction must never drive a community size below 1,
    even when the min-size bumps overshoot the floor() undershoot (many
    tail communities at extreme skew): N stays M·nodes_per_part exactly."""
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=n_c, attach=1, seed=0, feat_dim=4, size_skew=skew)
    sizes = np.bincount(part, minlength=m)
    assert g.num_nodes == m * n_c
    assert sizes.sum() == m * n_c and (sizes >= 1).all()


@pytest.fixture(scope="module")
def skewed_layout():
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=8, nodes_per_part=24, attach=2, seed=0, feat_dim=8,
        size_skew=0.9)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    return g, layout


def test_bucketed_layout_row_counts(skewed_layout):
    g, layout = skewed_layout
    counts = layout.eff_row_counts()
    assert layout.pad_mode == "bucketed"
    assert (counts >= layout.sizes).all()
    assert (counts <= layout.n_pad).all()
    # skewed sizes ⇒ strictly less logical padding than the global scheme
    global_pad = layout.num_parts * layout.n_pad - int(layout.sizes.sum())
    assert 0 < layout.pad_rows < global_pad
    # the BlockCSR carries the same ragged metadata
    csr = layout.compress()
    rows, nbrs = csr.ell_row_counts()
    np.testing.assert_array_equal(rows, counts)
    # nbr counts are the row counts of the indexed community, zero on pads
    for m in range(layout.num_parts):
        for d in range(csr.max_deg):
            expect = counts[csr.ell_indices[m, d]] if csr.ell_mask[m, d] \
                else 0
            assert nbrs[m, d] == expect


def test_blocks_are_zero_outside_row_counts(skewed_layout):
    """The contract the kernel guards rely on: every stored block is zero
    outside its (row_counts[m], row_counts[r]) corner."""
    _, layout = skewed_layout
    counts = layout.eff_row_counts()
    for m in range(layout.num_parts):
        for r in range(layout.num_parts):
            blk = layout.a_blocks[m, r]
            assert np.abs(blk[counts[m]:, :]).sum() == 0.0
            assert np.abs(blk[:, counts[r]:]).sum() == 0.0


def test_blockify_roundtrip_and_size(skewed_layout):
    g, layout = skewed_layout
    rng = np.random.default_rng(0)
    x = rng.normal(size=(g.num_nodes, 5)).astype(np.float32)
    b = layout.blockify(x)
    # ragged total: Σ bucket rows — strictly below the M·n_pad pack
    assert b.shape[0] == int(layout.eff_row_counts().sum())
    assert b.shape[0] < layout.num_parts * layout.n_pad
    np.testing.assert_array_equal(layout.unblockify(b), x)
    # offsets partition the ragged rows
    offs = layout.row_offsets()
    assert offs[0] == 0 and offs[-1] == b.shape[0]


def test_blockify_empty_and_singleton_communities():
    """Forced num_parts keeps trailing/interior empty communities; blockify
    must round-trip with 0-row and 1-node communities present."""
    n = 7
    edges = np.array([[0, 1], [1, 2], [3, 4]], dtype=np.int32)
    part = np.array([0, 0, 0, 2, 2, 2, 4], dtype=np.int32)  # 1, 3 empty
    layout = graph.build_community_layout(n, edges, part, num_parts=6,
                                          pad_mode="bucketed")
    assert layout.num_parts == 6
    assert layout.sizes.tolist() == [3, 0, 3, 0, 1, 0]
    counts = layout.eff_row_counts()
    assert counts[1] == counts[3] == counts[5] == 0   # empty: zero rows
    assert counts[4] == 8                             # singleton: min bucket
    x = np.arange(n, dtype=np.float32)[:, None]
    np.testing.assert_array_equal(layout.unblockify(layout.blockify(x)), x)
    # pack/unpack agree on the same forced layout
    np.testing.assert_array_equal(layout.unpack(layout.pack(x)), x)


# ---------------------------------------------------------------------------
# pad accounting
# ---------------------------------------------------------------------------

def test_pad_stats_accounting(skewed_layout):
    _, layout = skewed_layout
    dims = [16, 8]
    bucketed = messages.pad_stats(layout.neighbor_mask, layout.sizes,
                                  layout.row_counts, layout.n_pad, dims)
    glob = messages.pad_stats(layout.neighbor_mask, layout.sizes, None,
                              layout.n_pad, dims)
    assert bucketed["pad_rows"] == layout.pad_rows
    assert bucketed["pad_bytes"] == layout.pad_rows * sum(dims) * 4
    assert bucketed["pad_bytes"] < glob["pad_bytes"]
    assert bucketed["pad_flops"] < glob["pad_flops"]
    # both schemes process at least the true rows; global processes n_pad
    assert bucketed["true_rows_total"] == glob["true_rows_total"] \
        == int(layout.sizes.sum())
    assert glob["padded_rows_total"] == layout.num_parts * layout.n_pad
    assert 0.0 <= bucketed["pad_flop_frac"] < glob["pad_flop_frac"] < 1.0
    with pytest.raises(ValueError):
        messages.pad_stats(layout.neighbor_mask, layout.sizes,
                           np.zeros(layout.num_parts), layout.n_pad, dims)


# ---------------------------------------------------------------------------
# row-exact exchange
# ---------------------------------------------------------------------------

def test_row_exact_wire_tracks_true_sizes(skewed_layout):
    """Row-exact scheduled wire == Σ true rows over wired messages (plus
    bounded round padding), strictly below the whole-block schedule."""
    _, layout = skewed_layout
    for n_shards in (2, 4, 8):
        whole = messages.build_neighbor_exchange(
            layout.neighbor_mask, n_shards, layout.n_pad)
        exact = messages.build_neighbor_exchange(
            layout.neighbor_mask, n_shards, layout.n_pad,
            sizes=layout.sizes)
        sw = messages.exchange_bytes(whole, [8])
        se = messages.exchange_bytes(exact, [8])
        assert se["wire_bytes"] < sw["wire_bytes"]
        assert se["p2p_needed_bytes"] < sw["p2p_needed_bytes"]
        # the true rows of every wired message are exact community sizes
        k = exact.lanes_per_shard
        expect = 0
        for dst in range(n_shards):
            for r in exact.needed_ids[dst]:
                if r // k != dst:
                    expect += int(layout.sizes[r])
        assert se["true_rows"] == expect
    with pytest.raises(ValueError):
        messages.build_neighbor_exchange(layout.neighbor_mask, 2,
                                         layout.n_pad,
                                         sizes=layout.sizes + layout.n_pad)


def test_row_exact_exchange_delivers_host_sim(skewed_layout):
    """Numpy simulation of exchange_neighbors over the row-exact plan:
    every shard ends with exactly the payload rows of its needed ids (pad
    rows zero), matching the lane-major slot map."""
    _, layout = skewed_layout
    m, n = layout.num_parts, layout.n_pad
    rng = np.random.default_rng(1)
    x = rng.normal(size=(m, n, 3)).astype(np.float32)
    for c in range(m):
        x[c, int(layout.sizes[c]):] = 0.0          # trainer invariant
    for n_shards in (2, 4):
        plan = messages.build_neighbor_exchange(
            layout.neighbor_mask, n_shards, n, sizes=layout.sizes)
        k = plan.lanes_per_shard
        for s in range(n_shards):
            x_flat = x[s * k:(s + 1) * k].reshape(k * n, -1)
            buf = np.zeros((plan.r_pad * n, 3), np.float32)
            own = (plan.own_slots[s][:, None] * n
                   + np.arange(n)[None, :]).reshape(-1)
            buf[own] = x_flat
            for rnd in plan.rounds:
                for src, dst in rnd.pairs:
                    if dst != s:
                        continue
                    payload = x[src * k:(src + 1) * k].reshape(
                        k * n, -1)[rnd.send_idx[src]]
                    keep = rnd.recv_slot[dst] < plan.r_pad * n
                    buf[rnd.recv_slot[dst][keep]] = payload[keep]
            buf = buf.reshape(plan.r_pad, n, 3)
            for slot, gid in enumerate(plan.needed_ids[s]):
                np.testing.assert_array_equal(buf[slot], x[gid])
            for slot in range(len(plan.needed_ids[s]), plan.r_pad):
                assert np.abs(buf[slot]).max() == 0.0


# ---------------------------------------------------------------------------
# trainer A/B: ragged vs global padding
# ---------------------------------------------------------------------------

def _skewed_trainer_case():
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=4, nodes_per_part=16, attach=1, seed=2, feat_dim=8,
        size_skew=0.8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    return g, part, cfg, admm


def test_trainer_pad_modes_bit_compatible_and_stats_drop():
    """pad_mode only changes what is processed/wired: global and bucketed
    trainers produce identical W/Z/U and Lagrangian, while the bucketed
    comm_stats record strictly less padding — on the axes whose consumer
    is actually engaged (row-exact p2p wire; guarded kernel with
    use_kernel)."""
    g, part, cfg, admm = _skewed_trainer_case()
    glob = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                               compressed=True, pad_mode="global",
                               use_kernel=True)
    buck = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                               compressed=True, pad_mode="bucketed",
                               use_kernel=True)
    assert glob.comm_stats["pad_mode"] == "global"
    assert buck.comm_stats["pad_mode"] == "bucketed"
    assert buck.comm_stats["pad_guards"] == {"kernel": True, "wire": True}
    assert buck.comm_stats["pad_bytes"] < glob.comm_stats["pad_bytes"]
    assert buck.comm_stats["pad_flops"] < glob.comm_stats["pad_flops"]
    # stats are gated on the consumer: without the guarded kernel the
    # einsum aggregation processes every n_pad row, so bucketed pad_flops
    # must NOT claim the skip; an allgather transport wires full-pad
    # payloads, so bucketed pad_bytes must not claim the wire win either
    nok = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                              compressed=True, pad_mode="bucketed")
    assert nok.comm_stats["pad_guards"] == {"kernel": False, "wire": True}
    assert nok.comm_stats["pad_flops"] == glob.comm_stats["pad_flops"]
    assert nok.comm_stats["pad_bytes"] == buck.comm_stats["pad_bytes"]
    nag = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                              compressed=True, pad_mode="bucketed",
                              transport="allgather")
    assert nag.comm_stats["pad_guards"]["wire"] is False
    assert nag.comm_stats["pad_bytes"] == glob.comm_stats["pad_bytes"]
    for _ in range(3):
        glob.step()
        buck.step()
    for za, zb in zip(glob.state.zs, buck.state.zs):
        np.testing.assert_allclose(np.asarray(za), np.asarray(zb),
                                   rtol=2e-4, atol=2e-5)
    for wa, wb in zip(glob.state.weights, buck.state.weights):
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wb),
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(glob.state.u),
                               np.asarray(buck.state.u),
                               rtol=2e-4, atol=2e-5)
    lg = float(glob._lagrangian(glob.state))
    lb = float(buck._lagrangian(buck.state))
    assert lb == pytest.approx(lg, rel=1e-5)
    with pytest.raises(ValueError):
        ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                            compressed=True, pad_mode="diagonal")


def test_trainer_kernel_interpret_with_ragged_counts():
    """The interpret-mode Pallas ELL kernel under ragged row counts matches
    the einsum path through a full ADMM step on a skewed layout."""
    from repro.kernels import ops as kops

    g, part, cfg, admm = _skewed_trainer_case()
    base = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                               compressed=True, pad_mode="bucketed")
    base.step()
    kops.repro_force_interpret(True)
    try:
        kern = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0,
                                   part=part, compressed=True,
                                   pad_mode="bucketed", use_kernel=True)
        kern.step()
    finally:
        kops.repro_force_interpret(False)
    for zb, zk in zip(base.state.zs, kern.state.zs):
        np.testing.assert_allclose(np.asarray(zb), np.asarray(zk),
                                   rtol=2e-4, atol=2e-5)
    for wb, wk in zip(base.state.weights, kern.state.weights):
        np.testing.assert_allclose(np.asarray(wb), np.asarray(wk),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# bf16 ELL block store
# ---------------------------------------------------------------------------

def test_adjacency_bf16_halves_blocks_and_stays_close():
    """CommunityData(adjacency_bf16=True): bf16 resident blocks (halved
    bytes, itemsize-aware accounting) with f32 accumulation — parity with
    the f32 store at loose tolerance over 3 iterations."""
    g, part, cfg, admm = _skewed_trainer_case()
    f32 = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                              compressed=True)
    b16 = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                              compressed=True, adjacency_bf16=True)
    assert b16.data.adjacency_bf16 and not f32.data.adjacency_bf16
    assert b16.data.ell_blocks.dtype == jnp.bfloat16
    # exactly the block plane halves; indices/mask stay full precision
    assert b16.data.ell_blocks.nbytes * 2 == f32.data.ell_blocks.nbytes
    assert b16.data.adjacency_nbytes < f32.data.adjacency_nbytes
    # the analytic accounting tracks the actual resident bytes
    assert b16.comm_stats["adjacency"]["ell_bytes"] == \
        b16.data.adjacency_nbytes
    assert b16.comm_stats["adjacency"]["block_itemsize"] == 2
    for _ in range(3):
        f32.step()
        b16.step()
    for zf, zb in zip(f32.state.zs, b16.state.zs):
        np.testing.assert_allclose(np.asarray(zf), np.asarray(zb),
                                   rtol=0.05, atol=0.05)
    for wf, wb in zip(f32.state.weights, b16.state.weights):
        np.testing.assert_allclose(np.asarray(wf), np.asarray(wb),
                                   rtol=0.05, atol=0.05)
    with pytest.raises(ValueError):
        ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                            adjacency_bf16=True)      # dense + bf16 store


# ---------------------------------------------------------------------------
# 4-shard subprocess: ragged p2p trainer vs the serial trainer
# ---------------------------------------------------------------------------

_RAGGED_WORKER = r"""
import jax
import numpy as np
from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer
from repro.core.serial import SerialADMMTrainer
from repro.core.subproblems import ADMMConfig
from jax.sharding import AxisType

N_SHARDS = 4
assert len(jax.devices()) >= N_SHARDS, jax.devices()
g, part = graph.synthetic_powerlaw_communities(
    num_parts=12, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
    size_skew=0.9)
sizes = np.bincount(part, minlength=12)
assert sizes.max() >= 2 * sizes.min()          # genuinely skewed
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh = jax.make_mesh((N_SHARDS,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:N_SHARDS])

serial = SerialADMMTrainer(cfg, admm, g, seed=0)
rag = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, part=part,
                          mesh=mesh, compressed=True, pad_mode="bucketed")
glo = ParallelADMMTrainer(cfg, admm, g, num_parts=12, seed=0, part=part,
                          mesh=mesh, compressed=True, pad_mode="global")
assert rag.transport == "p2p" and rag.comm_stats["pad_mode"] == "bucketed"
assert rag.comm_stats["wire_bytes"] < glo.comm_stats["wire_bytes"]
assert rag.comm_stats["pad_bytes"] < glo.comm_stats["pad_bytes"]
for _ in range(3):
    serial.step(); rag.step(); glo.step()

# ragged == global bit-compatible on the same mesh
for za, zb in zip(rag.state.zs, glo.state.zs):
    np.testing.assert_allclose(np.asarray(za), np.asarray(zb),
                               rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(np.asarray(rag.state.u), np.asarray(glo.state.u),
                           rtol=2e-4, atol=2e-5)
print("PAD_PARITY_OK")

# ragged p2p == the serial trainer (W/Z/U + Lagrangian)
for zs_, zp in zip(serial.state.zs, rag.state.zs):
    np.testing.assert_allclose(np.asarray(zs_),
                               rag.layout.unpack(np.asarray(zp)),
                               rtol=2e-3, atol=2e-4)
for ws, wp in zip(serial.state.weights, rag.state.weights):
    np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                               rtol=2e-3, atol=2e-4)
np.testing.assert_allclose(np.asarray(serial.state.u),
                           rag.layout.unpack(np.asarray(rag.state.u)),
                           rtol=2e-3, atol=2e-4)
lag_s = float(serial._lagr(serial.a_tilde, serial.z0, serial.labels,
                           serial.train_mask, serial.state))
lag_r = float(rag._lagrangian(rag.state))
assert abs(lag_s - lag_r) <= 1e-4 * max(1.0, abs(lag_s)), (lag_s, lag_r)
print("SERIAL_PARITY_OK")

# the ragged p2p step still compiles gather-free (analysis rule proof)
from repro import analysis
rep = analysis.analyze_trainer(rag, config="ragged-p2p")
assert analysis.no_findings(rep, rule="collective/no-allgather-under-p2p")
assert analysis.no_findings(rep, rule="collective/permute-schedule")
assert not rep.errors(), rep.summary()
print("HLO_OK")
"""


def test_ragged_p2p_matches_serial_on_4_shards():
    """The acceptance run: a 4-shard ragged (bucketed, row-exact p2p)
    trainer on a size-skewed graph matches the serial trainer's W/Z/U and
    Lagrangian after 3 iterations, wires strictly fewer bytes than the
    global-pad trainer, and compiles without an all-gather."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _RAGGED_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("PAD_PARITY_OK", "SERIAL_PARITY_OK", "HLO_OK"):
        assert tag in out.stdout, out.stdout
