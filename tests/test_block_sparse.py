"""Block-sparsity end-to-end: masked aggregation (einsum / ref oracle /
interpret-mode Pallas), the block-compressed (CSR-of-blocks / ELL) layout,
and the parallel trainer that holds only the ELL rows.

These run without hypothesis; test_property.py has generative versions.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph, messages
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.community_spmm import community_spmm as pallas_spmm


@pytest.fixture(scope="module")
def sparse_layout():
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=6, nodes_per_part=24, attach=1, seed=0, feat_dim=12)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True)
    return g, layout


def test_powerlaw_layout_is_block_sparse(sparse_layout):
    _, layout = sparse_layout
    m = layout.num_parts
    nbr = np.asarray(layout.neighbor_mask)
    assert nbr.diagonal().all()
    assert nbr.sum() < m * m, "power-law community graph must have absent blocks"
    # absent blocks are exactly zero in the dense layout
    absent = layout.a_blocks[~nbr]
    assert absent.size and np.abs(absent).max() == 0.0


def test_masked_spmm_all_paths_agree(sparse_layout):
    g, layout = sparse_layout
    rng = np.random.default_rng(0)
    c = 8
    z = jnp.asarray(layout.pack(
        rng.normal(size=(g.num_nodes, c)).astype(np.float32)))
    a = jnp.asarray(layout.a_blocks)
    nbr = jnp.asarray(layout.neighbor_mask)
    dense = jnp.einsum("mrip,rpc->mic", a, z)

    for me in range(layout.num_parts):
        oracle = ref.community_spmm_ref(a[me], z, nbr[me])
        np.testing.assert_allclose(np.asarray(oracle), np.asarray(dense[me]),
                                   rtol=1e-4, atol=1e-4)
        pallas = pallas_spmm(a[me], z, nbr[me], interpret=True)
        np.testing.assert_allclose(np.asarray(pallas), np.asarray(dense[me]),
                                   rtol=1e-4, atol=1e-4)

    # lane-batched dispatch with per-lane neighbour rows (the trainer path)
    lanes = kops.community_spmm(a, z, nbr)
    np.testing.assert_allclose(np.asarray(lanes), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_block_csr_roundtrip_and_ell_spmm(sparse_layout):
    g, layout = sparse_layout
    csr = layout.compress()
    assert csr is layout.block_csr          # cached when compressed=True
    assert csr.nnz == layout.nnz_blocks < layout.num_parts ** 2
    np.testing.assert_array_equal(csr.to_dense(), layout.a_blocks)

    rng = np.random.default_rng(1)
    x = rng.normal(size=(g.num_nodes, 5)).astype(np.float32)
    z = layout.pack(x)
    dense = np.einsum("mrip,rpc->mic", layout.a_blocks, z)
    np.testing.assert_allclose(csr.spmm(z), dense, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(layout.unpack(z), x, rtol=0, atol=0)

    zj = jnp.asarray(z)
    ell = kops.community_spmm_ell(jnp.asarray(csr.ell_blocks),
                                  jnp.asarray(csr.ell_indices),
                                  jnp.asarray(csr.ell_mask), zj)
    np.testing.assert_allclose(np.asarray(ell), dense, rtol=1e-4, atol=1e-4)
    oracle = ref.community_spmm_ell_ref(jnp.asarray(csr.ell_blocks),
                                        jnp.asarray(csr.ell_indices),
                                        jnp.asarray(csr.ell_mask), zj)
    np.testing.assert_allclose(np.asarray(oracle), dense,
                               rtol=1e-4, atol=1e-4)

    # compression is where the memory drops: nnz blocks vs M² blocks
    assert csr.blocks.nbytes < layout.a_blocks.nbytes


def test_gather_bytes_accounting(sparse_layout):
    _, layout = sparse_layout
    stats = messages.gather_bytes(layout.neighbor_mask, layout.n_pad, [16, 8])
    assert stats["needed_bytes"] < stats["full_bytes"]
    assert stats["nnz_blocks"] == layout.nnz_blocks
    assert 0.0 < stats["savings_ratio"] < 1.0
    # exact: needed/full == nnz/M²
    ratio = stats["needed_bytes"] / stats["full_bytes"]
    assert ratio == pytest.approx(layout.nnz_blocks / layout.num_parts ** 2)


def test_compressed_trainer_no_dense_blocks_and_parity():
    """The trainer holds NO dense (M, M, n_pad, n_pad) tensor — only the
    sharded ELL rows — and its host-side (BlockCSR), device-side
    (CommunityData) and analytic (messages.adjacency_bytes) accountings
    agree; after 3 ADMM iterations the iterates are finite and the
    Lagrangian equals the global one on the unpacked state."""
    from repro.core import gcn, subproblems
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
    from repro.core.subproblems import ADMMConfig, ADMMState

    g, part = graph.synthetic_powerlaw_communities(
        num_parts=4, nodes_per_part=16, attach=1, seed=2, feat_dim=8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)

    comp = ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0, part=part,
                               config=TrainerConfig.packed())
    assert not hasattr(comp.data, "a_blocks")
    csr = comp.layout.block_csr
    assert comp.data.ell_blocks.shape == (4, csr.max_deg,
                                          comp.layout.n_pad,
                                          comp.layout.n_pad)
    adj = comp.comm_stats["adjacency"]
    # the ELL store is strictly smaller than the dense tensor would be
    assert comp.data.adjacency_nbytes < adj["dense_bytes"]
    assert csr.ell_nbytes == comp.data.adjacency_nbytes
    # and the recorded accounting matches what is actually resident
    assert adj["resident_bytes"] == comp.data.adjacency_nbytes
    assert adj["ell_bytes"] == comp.data.adjacency_nbytes

    for _ in range(3):
        comp.step()
    dl, lay = comp.packed_layout, comp.layout

    def nodes(x):
        return jnp.asarray(lay.unpack(dl.unpack_state(np.asarray(x))))
    st = ADMMState(comp.state.weights, tuple(nodes(z) for z in comp.state.zs),
                   nodes(comp.state.u), comp.state.taus, comp.state.thetas)
    assert all(np.isfinite(np.asarray(z)).all() for z in st.zs)
    a = jnp.asarray(graph.normalized_adjacency(g.num_nodes, g.edges))
    ref_val = subproblems.lagrangian_value(
        cfg, admm, a, jnp.asarray(g.features), jnp.asarray(g.labels),
        jnp.asarray(g.train_mask, jnp.float32), st)
    assert float(comp._lagrangian(comp.state)) == \
        pytest.approx(float(ref_val), rel=1e-5)


def test_compressed_trainer_kernel_path():
    """use_kernel=True in compressed mode routes aggregation through the
    Pallas ELL kernel (CPU ref dispatch and interpret-mode body) and must
    match the einsum path."""
    from repro.core import gcn
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
    from repro.core.subproblems import ADMMConfig

    g, part = graph.synthetic_powerlaw_communities(
        num_parts=3, nodes_per_part=16, attach=1, seed=2, feat_dim=8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)

    base = ParallelADMMTrainer(cfg, admm, g, num_parts=3, seed=0, part=part,
                               config=TrainerConfig.packed())
    base.step()
    for interpret in (False, True):
        kops.repro_force_interpret(interpret)
        try:
            kern = ParallelADMMTrainer(
                cfg, admm, g, num_parts=3, seed=0, part=part,
                config=TrainerConfig.packed(use_kernel=True))
            kern.step()
        finally:
            kops.repro_force_interpret(False)
        for zb, zk in zip(base.state.zs, kern.state.zs):
            np.testing.assert_allclose(np.asarray(zb), np.asarray(zk),
                                       rtol=2e-4, atol=2e-5)
        for wb, wk in zip(base.state.weights, kern.state.weights):
            np.testing.assert_allclose(np.asarray(wb), np.asarray(wk),
                                       rtol=2e-4, atol=2e-5)


_MULTISHARD_WORKER = r"""
import jax
import numpy as np
from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.serial import SerialADMMTrainer
from repro.core.subproblems import ADMMConfig
from jax.sharding import AxisType

assert len(jax.devices()) >= 2, jax.devices()
g, part = graph.synthetic_powerlaw_communities(
    num_parts=4, nodes_per_part=16, attach=1, seed=3, feat_dim=8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh2 = jax.make_mesh((2,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:2])
mesh1 = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:1])

def build(mesh, num_parts=4, part=part):
    return ParallelADMMTrainer(cfg, admm, g, num_parts=num_parts, seed=0,
                               part=part, mesh=mesh,
                               config=TrainerConfig.packed())

# shard-count invariance: the same M on a 2-shard (k=2 lanes per shard)
# and a 1-shard mesh
comp2 = build(mesh2)
comp1 = build(mesh1)
for _ in range(3):
    comp2.step(); comp1.step()
lay = comp1.layout

def nodes(tr, x):
    return lay.unpack(tr.packed_layout.unpack_state(np.asarray(x)))

for zb, zc in zip(comp2.state.zs, comp1.state.zs):
    np.testing.assert_allclose(nodes(comp2, zb), nodes(comp1, zc),
                               rtol=2e-4, atol=2e-5)
for wb, wc in zip(comp2.state.weights, comp1.state.weights):
    np.testing.assert_allclose(np.asarray(wb), np.asarray(wc),
                               rtol=2e-4, atol=2e-5)

# serial vs parallel (M=1): identical subproblems, one agent
s = SerialADMMTrainer(cfg, admm, g, seed=0)
p = build(None, num_parts=1, part=None)
for _ in range(3):
    s.step(); p.step()
for ws, wp in zip(s.state.weights, p.state.weights):
    np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                               rtol=2e-4, atol=2e-6)
np.testing.assert_allclose(np.asarray(s.state.zs[-1]),
                           nodes(p, p.state.zs[-1]),
                           rtol=2e-3, atol=2e-4)
print("PARITY_OK")
"""


def test_parity_on_multi_shard_mesh():
    """Serial-vs-parallel parity at M=1 and 2-shard-vs-1-shard parity on a
    real 2-shard host mesh (subprocess: XLA locks the device count at
    first init)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _MULTISHARD_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PARITY_OK" in out.stdout


def test_parallel_lagrangian_matches_global():
    """TrainLog.lagrangian must be the true augmented Lagrangian: the packed
    per-epoch value equals subproblems.lagrangian_value on unpacked state."""
    import jax.numpy as jnp

    from repro.core import gcn, subproblems
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
    from repro.core.subproblems import ADMMConfig, ADMMState

    g, part = graph.synthetic_powerlaw_communities(
        num_parts=3, nodes_per_part=16, attach=1, seed=2, feat_dim=8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)

    p = ParallelADMMTrainer(cfg, admm, g, num_parts=3, seed=0, part=part,
                            config=TrainerConfig.packed())
    log = p.train(2)
    lay, dl = p.layout, p.packed_layout

    def nodes(x):
        return jnp.asarray(lay.unpack(dl.unpack_state(np.asarray(x))))
    zs = tuple(nodes(z) for z in p.state.zs)
    u = nodes(p.state.u)
    st = ADMMState(p.state.weights, zs, u, p.state.taus, p.state.thetas)
    a = jnp.asarray(graph.normalized_adjacency(g.num_nodes, g.edges))
    ref_val = subproblems.lagrangian_value(
        cfg, admm, a, jnp.asarray(g.features), jnp.asarray(g.labels),
        jnp.asarray(g.train_mask, jnp.float32), st)
    assert log.lagrangian[-1] == pytest.approx(float(ref_val), rel=1e-4)
    assert log.lagrangian[-1] != 0.0
