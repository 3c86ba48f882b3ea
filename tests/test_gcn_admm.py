"""ADMM core behaviour tests: convergence, message faithfulness, serial vs
parallel agreement (the paper's 'no performance loss' claim)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gcn, graph, messages, subproblems
from repro.core.serial import BaselineTrainer, SerialADMMTrainer
from repro.core.subproblems import ADMMConfig


@pytest.fixture(scope="module")
def tiny():
    g = graph.synthetic_sbm("amazon_photo_mini", seed=0)
    cfg = gcn.GCNConfig(layer_dims=(745, 64, 8))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    return g, cfg, admm


def test_forward_shapes(tiny):
    g, cfg, _ = tiny
    a = jnp.asarray(graph.normalized_adjacency(g.num_nodes, g.edges))
    ws = gcn.init_weights(cfg, jax.random.key(0))
    zs = gcn.forward(cfg, a, jnp.asarray(g.features), ws)
    assert zs[0].shape == (g.num_nodes, 64)
    assert zs[1].shape == (g.num_nodes, 8)
    assert all(np.isfinite(np.asarray(z)).all() for z in zs)


def test_serial_admm_decreases_lagrangian_and_learns(tiny):
    g, cfg, admm = tiny
    tr = SerialADMMTrainer(cfg, admm, g, seed=0)
    log = tr.train(15)
    assert log.train_acc[-1] > 0.6, log.train_acc
    assert log.test_acc[-1] > 0.6
    assert np.isfinite(log.lagrangian).all()


@pytest.mark.slow
def test_parallel_matches_serial_one_community(tiny):
    """M=1 parallel == serial (same subproblems, one agent)."""
    from repro.core.parallel import ParallelADMMTrainer
    g, cfg, admm = tiny
    s = SerialADMMTrainer(cfg, admm, g, seed=0)
    p = ParallelADMMTrainer(cfg, admm, g, num_parts=1, seed=0)
    for _ in range(3):
        s.step()
        p.step()
    for ws, wp in zip(s.state.weights, p.state.weights):
        np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                                   rtol=2e-4, atol=2e-6)
    z_s = np.asarray(s.state.zs[-1])
    z_p = p.layout.unpack(
        p.packed_layout.unpack_state(np.asarray(p.state.zs[-1])))
    np.testing.assert_allclose(z_s, z_p, rtol=2e-3, atol=2e-4)


@pytest.mark.slow
def test_parallel_communities_converge(tiny):
    """M=3 parallel ADMM reaches comparable accuracy to serial (paper §4.2:
    kept inter-community edges => no performance loss)."""
    from repro.core.parallel import ParallelADMMTrainer
    g, cfg, admm = tiny
    s = SerialADMMTrainer(cfg, admm, g, seed=0)
    p = ParallelADMMTrainer(cfg, admm, g, num_parts=3, seed=0)
    slog = s.train(15)
    plog = p.train(15)
    assert plog.test_acc[-1] > 0.6
    assert abs(plog.test_acc[-1] - slog.test_acc[-1]) < 0.15


def test_w_update_identical_serial_vs_parallel(tiny):
    """The W subproblem is a global objective in both trainers — first
    iteration W updates must agree to float tolerance."""
    from repro.core.parallel import ParallelADMMTrainer
    g, cfg, admm = tiny
    s = SerialADMMTrainer(cfg, admm, g, seed=0)
    p = ParallelADMMTrainer(cfg, admm, g, num_parts=3, seed=0)
    s.step()
    p.step()
    for ws, wp in zip(s.state.weights, p.state.weights):
        np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                                   rtol=1e-4, atol=1e-6)


def test_message_identities(tiny):
    """Appendix A eq. 4: relayed second-order info equals the literal
    per-neighbour message formulas, and neighbour pre-activations equal the
    global aggregation."""
    g, cfg, admm = tiny
    m = 3
    part = graph.partition_graph(g.num_nodes, g.edges, m, seed=0)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part)
    rng = np.random.default_rng(0)
    n_pad = layout.n_pad
    c_l, c_next = 16, 12
    z_all = jnp.asarray(rng.normal(size=(m, n_pad, c_l)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(c_l, c_next)).astype(np.float32))
    a_blocks = jnp.asarray(layout.a_blocks)

    for me in range(m):
        a_row = a_blocks[me]                       # Ã_{me, ·}
        # p_{l,r→me} = Ã_{me,r} Z_r W
        p = messages.first_order_messages(a_row, z_all, w)
        for r in range(m):
            expect = layout.a_blocks[me, r] @ np.asarray(z_all[r]) @ np.asarray(w)
            np.testing.assert_allclose(np.asarray(p[r]), expect, atol=1e-4)
        # q_me = Σ_r p_{l,r→me}
        q = messages.relay_aggregate(a_row, z_all, w)
        np.testing.assert_allclose(np.asarray(q), np.asarray(p.sum(0)),
                                   atol=1e-4)

    # s²_{l,r→me} = q_r − Ã_{r,me} Z_me W  ==  Σ_{r'≠me} Ã_{r,r'} Z_r' W
    me = 0
    q_all = jnp.stack([messages.relay_aggregate(a_blocks[r], z_all, w)
                       for r in range(m)])
    s2 = messages.second_order_from_relay(q_all, a_blocks[me], z_all[me], w)
    for r in range(m):
        expect = sum(layout.a_blocks[r, rp] @ np.asarray(z_all[rp])
                     for rp in range(m) if rp != me) @ np.asarray(w)
        np.testing.assert_allclose(np.asarray(s2[r]), expect, atol=1e-4)

    # neighbour pre-activations at z_var = z_ref reduce to q_all
    pre = messages.neighbor_preactivations(q_all, a_blocks[me], z_all[me],
                                           z_all[me], w)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(q_all), atol=1e-5)


def test_fista_solves_prox(tiny):
    """FISTA on eq. (7) decreases its objective and beats the init."""
    g, cfg, admm = tiny
    rng = np.random.default_rng(0)
    n, c = 64, 8
    b = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    u = jnp.asarray(0.01 * rng.normal(size=(n, c)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, c, n).astype(np.int32))
    mask = jnp.ones((n,), jnp.float32)
    z0 = jnp.zeros((n, c))

    def obj(z):
        r = z - b
        return (gcn.masked_cross_entropy(z, labels, mask)
                + jnp.vdot(u, r) + 0.5 * admm.rho * jnp.vdot(r, r))

    admm_hi = ADMMConfig(nu=admm.nu, rho=admm.rho, fista_iters=25)
    z = subproblems.fista_last_z(admm_hi, b, u, labels, mask, z0)
    assert float(obj(z)) < float(obj(z0)) - 1e-3


def test_baseline_optimizers_learn(tiny):
    g, cfg, _ = tiny
    for opt, lr in [("adam", 1e-3), ("adagrad", 1e-3), ("gd", 1e-1)]:
        tr = BaselineTrainer(cfg, g, opt, lr, seed=0)
        log = tr.train(10)
        assert log.train_acc[-1] > log.train_acc[0], opt


def test_backtracking_satisfies_majorization():
    """Accepted τ satisfies the paper's P ≥ φ condition."""
    admm = ADMMConfig()
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(20, 20)).astype(np.float32))

    def obj(x):
        r = a @ x - 1.0
        return jnp.vdot(r, r).real

    x0 = jnp.asarray(rng.normal(size=(20, 5)).astype(np.float32))
    x1, tau = subproblems.backtracking_step(obj, x0, jnp.asarray(1.0), admm)
    val, grad = jax.value_and_grad(obj)(x0)
    p_val = val - 0.5 * jnp.vdot(grad, grad).real / tau
    assert float(obj(x1)) <= float(p_val) * (1 + 1e-5) + 1e-6
    assert float(obj(x1)) < float(val)
