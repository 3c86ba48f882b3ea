"""Fused aggregation→Z-update: the single-pass Pallas kernel, its
reassociated oracle, the TrainerConfig plumbing, and the
memory/fused-no-intermediate analysis rule.

The contract under test: ``fused=True`` changes WHERE the aggregated
``(k, n_pad, C)`` stack lives (VMEM scratch / never materialised), never
what a Z-update target computes.  The fused kernel's aggregate
accumulation is the packed kernel's bitwise; the closing GEMM
reassociates ``(A·Z)·W`` to ``A·(Z·W)``, so fused-vs-unfused parity is
per-iteration dot-order tolerance (≤1e-6 at GCN widths).  On one shard
the packed wire is off and ``fused=True`` is inert — the trainer stays
bitwise-identical to unfused.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.analysis.registry import AnalysisContext
from repro.analysis.rules.memory import (fused_agg_handoffs,
                                         fused_no_intermediate)
from repro.analysis.rules.pallas import (check_kernel_bounds,
                                         check_kernel_vmem)
from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig
from repro.kernels import ops, ref
from repro.kernels.community_spmm import (community_spmm_ell_fused,
                                          ell_fused_spec)


# ---------------------------------------------------------------------------
# the fused kernel vs its oracles
# ---------------------------------------------------------------------------

def _packed_inputs(k, max_deg, n_pad, c_in, c_out, seed=0):
    """Synthetic packed receive plane honouring the layout contract:
    8-aligned slot offsets, bucket row counts in multiples of 8, slots
    packed back to back."""
    rng = np.random.default_rng(seed)
    n_slots = k + 2
    counts = 8 * rng.integers(1, n_pad // 8 + 1, size=n_slots)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    plane_rows = int(counts.sum())
    slot = rng.integers(0, n_slots, size=(k, max_deg))
    ell_offsets = offsets[slot].astype(np.int32)
    nbr_counts = counts[slot].astype(np.int32)
    mask = np.zeros((k, max_deg), np.int32)
    for r in range(k):
        mask[r, : 1 + r % max_deg] = 1
    row_counts = (8 * rng.integers(1, n_pad // 8 + 1,
                                   size=k)).astype(np.int32)
    blocks = rng.normal(size=(k, max_deg, n_pad, n_pad)).astype(np.float32)
    # zero-outside-counts contract: adjacency rows past the lane's count
    # and columns past the neighbour's count are zero in packed tensors
    lane = np.arange(n_pad)
    blocks *= (lane[None, None, :, None] < row_counts[:, None, None, None])
    blocks *= (lane[None, None, None, :] < nbr_counts[:, :, None, None])
    z_plane = rng.normal(size=(plane_rows, c_in)).astype(np.float32)
    w = rng.normal(size=(c_in, c_out)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in
                 (blocks, ell_offsets, mask, z_plane, w, row_counts,
                  nbr_counts))


@pytest.mark.parametrize("k,max_deg,n_pad,c_in,c_out", [
    (2, 3, 32, 8, 8),       # square W (the hidden-layer target shape)
    (3, 2, 16, 8, 4),       # narrowing W (the output-layer shape)
    (2, 1, 64, 16, 8),      # single-neighbour rows
    (4, 4, 24, 4, 12),      # widening W, ragged fan-in
])
def test_fused_kernel_matches_oracles(k, max_deg, n_pad, c_in, c_out):
    """Interpret-mode fused kernel vs the reassociated einsum oracle vs
    the two-step packed-aggregate→GEMM reference."""
    args = _packed_inputs(k, max_deg, n_pad, c_in, c_out)
    blocks, off, mask, z_plane, w, rows, nbrs = args
    out = community_spmm_ell_fused(*args, interpret=True)
    oracle = ref.community_spmm_ell_fused_einsum(*args)
    agg = ref.community_spmm_ell_packed_einsum(blocks, off, mask, z_plane,
                                               rows, nbrs)
    two_step = agg @ w
    assert out.shape == (k, n_pad, c_out)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    # reassociation tolerance, not bitwise — the fused acceptance bound
    np.testing.assert_allclose(np.asarray(out), np.asarray(two_step),
                               rtol=1e-4, atol=1e-4)


def test_fused_kernel_respects_masks_and_row_counts():
    """Masked slots must not contribute and rows past a lane's count must
    stay zero — the same guards the packed kernel carries, now ahead of
    the in-kernel GEMM."""
    args = _packed_inputs(3, 3, 32, 8, 8, seed=5)
    blocks, off, mask, z_plane, w, rows, nbrs = args
    out = np.asarray(community_spmm_ell_fused(*args, interpret=True))
    lane = np.arange(32)
    for m in range(3):
        dead = out[m, lane >= int(rows[m])]
        np.testing.assert_array_equal(dead, np.zeros_like(dead))
    full = community_spmm_ell_fused(blocks, off, jnp.ones_like(mask),
                                    z_plane, w, rows, nbrs, interpret=True)
    assert np.abs(out - np.asarray(full)).max() > 1e-3


def test_fused_dispatch_cpu_is_the_oracle():
    """Off-TPU the ops wrapper dispatches to the reassociated einsum
    oracle at trace time — bitwise, which is what keeps the CPU-compiled
    fused step free of the aggregated intermediate."""
    args = _packed_inputs(2, 2, 16, 8, 4, seed=2)
    np.testing.assert_array_equal(
        np.asarray(ops.community_spmm_ell_fused(*args)),
        np.asarray(ref.community_spmm_ell_fused_einsum(*args)))


def test_fused_spec_passes_pallas_checks():
    """The shipped fused spec is clean under the bounds and VMEM rules
    with realistic packed scalars (benchmark widths)."""
    k, max_deg, n_pad, c = 2, 3, 256, 256
    plane_rows = 1024
    spec = ell_fused_spec(k, max_deg, n_pad, c, c, plane_rows)
    scalars = {"ell_offsets8": np.zeros((k, max_deg), np.int32),
               "ell_mask": np.ones((k, max_deg), np.int32),
               "row_counts": np.full((k,), n_pad, np.int32),
               "nbr_counts": np.full((k, max_deg), n_pad, np.int32)}
    assert not check_kernel_bounds(spec, scalars)
    assert not check_kernel_vmem(spec)
    # an offset table pointing past the plane must be flagged
    bad = dict(scalars, ell_offsets8=np.full((k, max_deg),
                                             plane_rows // 8, np.int32))
    findings = check_kernel_bounds(spec, bad)
    assert findings and findings[0].rule == "pallas/index-bounds"


# ---------------------------------------------------------------------------
# TrainerConfig plumbing
# ---------------------------------------------------------------------------

def _trainer(g, part, mesh, **kw):
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    m = int(part.max()) + 1
    return ParallelADMMTrainer(cfg, admm, g, num_parts=m, seed=0,
                               part=part, mesh=mesh,
                               config=TrainerConfig.packed(**kw))


def test_fused_one_shard_is_bitwise_inert():
    """On one shard there is no packed wire plane, the blocked body runs,
    and fused=True must change nothing — bitwise."""
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.8)
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    ref_tr = _trainer(g, part, mesh)
    fu_tr = _trainer(g, part, mesh, fused=True)
    for _ in range(3):
        ref_tr.step()
        fu_tr.step()
    for zr, zf in zip(ref_tr.state.zs, fu_tr.state.zs):
        np.testing.assert_array_equal(np.asarray(zr), np.asarray(zf))
    np.testing.assert_array_equal(np.asarray(ref_tr.state.u),
                                  np.asarray(fu_tr.state.u))
    for wr, wf in zip(ref_tr.state.weights, fu_tr.state.weights):
        np.testing.assert_array_equal(np.asarray(wr), np.asarray(wf))


# ---------------------------------------------------------------------------
# the memory/fused-no-intermediate rule
# ---------------------------------------------------------------------------

N_PAD = 16


def _toy_ops(seed=0):
    rng = np.random.default_rng(seed)
    blocks = jnp.asarray(rng.normal(size=(1, 2, N_PAD, N_PAD))
                         .astype(np.float32))
    z = jnp.asarray(rng.normal(size=(1, 2, N_PAD, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    return blocks, z, w


def test_fused_handoff_walk_counts_agg_to_dot():
    blocks, z, w = _toy_ops()

    def unfused(blocks, z, w):
        agg = jnp.einsum("mdip,mdpc->mic", blocks, z)   # (1, n_pad, 8)
        return agg @ w

    def fused(blocks, z, w):
        return jnp.einsum("mdip,mdpc->mic", blocks, z @ w)

    jx_u = jax.make_jaxpr(unfused)(blocks, z, w)
    jx_f = jax.make_jaxpr(fused)(blocks, z, w)
    assert len(fused_agg_handoffs(jx_u, N_PAD)) == 1
    assert len(fused_agg_handoffs(jx_f, N_PAD)) == 0


def test_fused_handoff_walk_follows_partial_sums_only():
    """Taint crosses the overlap path's add-of-partials into the dot, but
    does NOT leak through activations into downstream dots (the fused
    sites' own outputs feed the solvers legitimately)."""
    blocks, z, w = _toy_ops()

    def overlap_unfused(blocks, z, w):
        a = jnp.einsum("mdip,mdpc->mic", blocks, z)
        b = jnp.einsum("mdip,mdpc->mic", blocks, 2.0 * z)
        return (a + b) @ w                               # one handoff

    def fused_then_consumed(blocks, z, w):
        out = jnp.einsum("mdip,mdpc->mic", blocks, z @ w)   # (1, n_pad, 4)
        act = jax.nn.relu(out)                           # carrier break
        return act @ jnp.ones((4, 3), jnp.float32)       # no handoff

    assert len(fused_agg_handoffs(
        jax.make_jaxpr(overlap_unfused)(blocks, z, w), N_PAD)) == 1
    assert len(fused_agg_handoffs(
        jax.make_jaxpr(fused_then_consumed)(blocks, z, w), N_PAD)) == 0


def test_fused_no_intermediate_rule_fires_and_stays_silent():
    blocks, z, w = _toy_ops()

    def unfused(blocks, z, w):
        return jnp.einsum("mdip,mdpc->mic", blocks, z) @ w

    def fused(blocks, z, w):
        return jnp.einsum("mdip,mdpc->mic", blocks, z @ w)

    exp = {"n_pad": N_PAD, "fused": True, "fused_max_agg_handoffs": 0}

    def run(fn, expectations):
        ctx = AnalysisContext(
            hlo_text=None, jaxpr=jax.make_jaxpr(fn)(blocks, z, w),
            expectations=expectations, config="toy")
        return list(fused_no_intermediate(ctx))

    hits = run(unfused, exp)
    assert hits and hits[0].rule == "memory/fused-no-intermediate"
    assert hits[0].details["count"] == 1
    assert not run(fused, exp)
    # the W-update allowance: one surviving aggregate per layer is blessed
    assert not run(unfused, dict(exp, fused_max_agg_handoffs=1))
    # unfused configs are out of scope
    assert not run(unfused, {"n_pad": N_PAD, "fused": False})


# ---------------------------------------------------------------------------
# 4-shard subprocess: per-iteration parity, the compiled-step proof, and
# the rule firing on the unfused program under fused expectations
# ---------------------------------------------------------------------------

_FUSED_WORKER = r"""
import numpy as np, jax
import jax.numpy as jnp
from repro import analysis
from repro.analysis.rules.memory import fused_agg_handoffs
from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig
from jax.sharding import AxisType

g, part = graph.synthetic_powerlaw_communities(
    num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
    size_skew=0.8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:4])

def build(**kw):
    return ParallelADMMTrainer(cfg, admm, g, num_parts=8, seed=0,
                               part=part, mesh=mesh,
                               config=TrainerConfig.packed(**kw))

def delta(a, b):
    return max(
        max(float(jnp.max(jnp.abs(x - y)))
            for x, y in zip(a.weights, b.weights)),
        max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a.zs, b.zs)),
        float(jnp.max(jnp.abs(a.u - b.u))))

# --- per-iteration W/Z/U parity from a shared state: ≤ 1e-6 ---
un = build()
fu = build(fused=True)
state = un.state
for _ in range(3):
    fu_next = fu._step(jax.tree.map(jnp.copy, state))
    state = un._step(state)
    d = delta(state, fu_next)
    assert d <= 1e-6, f"fused parity {d} above 1e-6"
print("FU_PARITY_OK")

# --- the compiled fused step passes the analysis registry, the rule
#     counts exactly the W-update floor: one aggregate per layer past the
#     first, whose Ã Z_0 is the constructor's ---
n_pad = fu.layout.n_pad
fu_h = len(fused_agg_handoffs(jax.make_jaxpr(fu._step)(fu.state), n_pad))
un_h = len(fused_agg_handoffs(jax.make_jaxpr(un._step)(un.state), n_pad))
assert fu_h == cfg.num_layers - 1, (fu_h, cfg.num_layers)
assert un_h > fu_h, (un_h, fu_h)
rep = analysis.analyze_trainer(fu, config="p2p_fused")
assert analysis.no_findings(rep, rule="memory/fused-no-intermediate")
assert not rep.errors(), rep.summary()
print("FU_ANALYSIS_OK")

# --- the rule FIRES when the unfused program is held to the fused
#     contract (proves the proof is not vacuous) ---
from repro.analysis.registry import AnalysisContext
from repro.analysis.rules.memory import fused_no_intermediate
ctx = AnalysisContext(
    hlo_text=None, jaxpr=jax.make_jaxpr(un._step)(un.state),
    expectations={"n_pad": n_pad, "fused": True,
                  "fused_max_agg_handoffs": cfg.num_layers - 1},
    config="unfused-held-to-fused")
hits = list(fused_no_intermediate(ctx))
assert hits and hits[0].details["count"] == un_h, hits
print("FU_RULE_FIRES_OK")

# --- overlap composes: per-group fused aggregation, same handoff floor,
#     tolerance parity against the fused non-overlap trainer ---
ov = build(fused=True, overlap=True)
ov_h = len(fused_agg_handoffs(jax.make_jaxpr(ov._step)(ov.state), n_pad))
assert ov_h == cfg.num_layers - 1, ov_h
fu2 = build(fused=True)
for _ in range(3):
    ov.step(); fu2.step()
d = delta(ov.state, fu2.state)
assert d <= 1e-4, f"fused overlap parity {d}"
print("FU_OVERLAP_OK")
"""


def test_fused_on_4_shards():
    """The acceptance run: fused vs unfused per-iteration W/Z/U parity
    ≤1e-6 on 4 shards, the compiled fused step passes
    memory/fused-no-intermediate at the W-update floor, the rule fires on
    the unfused program under fused expectations, and overlap composes at
    the same floor."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _FUSED_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("FU_PARITY_OK", "FU_ANALYSIS_OK", "FU_RULE_FIRES_OK",
                "FU_OVERLAP_OK"):
        assert tag in out.stdout, out.stdout
