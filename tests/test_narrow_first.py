"""Narrow-first aggregation in the eval programs: where an Ã·Z·W
composite of the metrics or Lagrangian program narrows the width, it is
computed as Ã·(Z·W).  The round's dual update keeps (Ã Z⁺) W⁺, the
association the next W_L line search forms its residual with.  Ã Z_0
comes from the trainer's constructor (``ax``, aggregated once since
Z_0 = X never changes): the step and both eval programs multiply it, so
no program aggregates at C_0 but the fused wire's one-pass Z sites.

Parity: after one round from a shared state, the trainer's U and its
metrics and Lagrangian match the wide-first (Ã Z) W of the serial
trainer and ``subproblems`` on the same iterates, in every mode, at a
tolerance set by float32 rounding of the reordered sums.

Engagement: ``comm_stats["aggregations"]`` records the width of each
distinct aggregation a program traces, C_0 under "construct" alone; the
eval programs' jaxprs hold no aggregation at the hidden width, and no
program's at C_0; a layer that widens keeps wide-first; ``ax`` is the
step's historic Ã Z_0, bit for bit on the kernel paths and to float32
rounding on the einsum ones; the wire accounting prices the
gathers as the compiled step's permutes move them, Z_0 on the fused wire
only.

Run as a script (``python tests/test_narrow_first.py``) on a host with 4
devices, it prints the 4-shard modes' readings as one JSON line.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import gcn, graph, messages
from repro.core.parallel import (AXIS, ParallelADMMTrainer, TrainerConfig,
                                 gathered_widths)
from repro.core.serial import SerialADMMTrainer
from repro.core.subproblems import ADMMConfig, ADMMState
from repro.kernels import ops as kops

ADMM = ADMMConfig(nu=1e-3, rho=1e-3)
HIDDEN = 16
# float32 rounding of sums reordered over a few dozen terms (they read
# ~1.7e-7 here); the older mode-parity tests hold the iterates to 2e-4
TOL = 2e-6

MODES_1 = {
    "dense": TrainerConfig.dense(),
    "compressed": TrainerConfig.p2p(),
    "compressed-pallas-interpret": TrainerConfig.p2p(use_kernel=True),
    "packed": TrainerConfig.packed(),
}
MODES_4 = {
    "packed-p2p-4": TrainerConfig.packed(),
    "packed-overlap-4": TrainerConfig.packed(overlap=True),
    "fused-4": TrainerConfig.packed(fused=True),
}


def _graph():
    return graph.synthetic_powerlaw_communities(
        num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.8)


def _trainer(g, part, dims, config, mesh=None):
    return ParallelADMMTrainer(gcn.GCNConfig(layer_dims=dims), ADMM, g,
                               num_parts=int(part.max()) + 1, seed=0,
                               part=part, mesh=mesh, config=config)


def _nodes(tr, x):
    """A strided or packed trainer array as (N, C) node rows."""
    x = np.asarray(x)
    if tr.packed:
        x = tr.packed_layout.unpack_state(x)
    return jnp.asarray(tr.layout.unpack(x))


def round_readings(config, mesh=None) -> dict:
    """One round of a trainer in ``config`` from the state its first round
    left (U nonzero), against the wide-first serial computation on the
    same iterates: the largest error of U over its largest entry, the
    relative errors of the Lagrangian and the residual norm, and the
    accuracies' differences."""
    g, part = _graph()
    dims = (g.features.shape[1], HIDDEN, g.num_classes)
    tr = _trainer(g, part, dims, config, mesh)
    tr.step()
    u0 = _nodes(tr, tr.state.u)
    tr.step()
    zs = tuple(_nodes(tr, z) for z in tr.state.zs)
    ws = tuple(tr.state.weights)
    st = ADMMState(ws, zs, _nodes(tr, tr.state.u), tr.state.taus,
                   tr.state.thetas)

    serial = SerialADMMTrainer(tr.cfg, ADMM, g, seed=0)
    a = serial.a_tilde
    u_ref = u0 + ADMM.rho * (zs[-1] - a @ zs[-2] @ ws[-1])
    lag_ref = float(serial._lagr(a, serial.z0, serial.labels,
                                 serial.train_mask, st))
    tr_ref, te_ref, res_ref = (float(x) for x in serial._metrics(st))
    tr_acc, te_acc, res = (float(x) for x in tr._metrics(tr.state))
    lag = float(tr._lagrangian(tr.state))
    return {
        "u": float(jnp.max(jnp.abs(st.u - u_ref)) / jnp.max(jnp.abs(u_ref))),
        "lagrangian": abs(lag - lag_ref) / abs(lag_ref),
        "residual": abs(res - res_ref) / res_ref,
        "accuracy": max(abs(tr_acc - tr_ref), abs(te_acc - te_ref)),
    }


def _check(readings):
    assert readings["u"] <= TOL, readings
    assert readings["lagrangian"] <= TOL, readings
    assert readings["residual"] <= TOL, readings
    assert readings["accuracy"] == 0.0, readings


# ---------------------------------------------------------------------------
# parity with the wide-first serial computation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", sorted(MODES_1))
def test_round_matches_wide_first_serial(mode):
    kops.repro_force_interpret(mode.endswith("interpret"))
    try:
        readings = round_readings(MODES_1[mode])
    finally:
        kops.repro_force_interpret(False)
    _check(readings)


def _four_shard_main():
    mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,),
                         devices=jax.devices()[:4])
    out = {name: round_readings(cfg, mesh) for name, cfg in MODES_4.items()}
    g, part = _graph()
    dims = (g.features.shape[1], HIDDEN, g.num_classes)
    out["widths"] = {
        name: _program_widths(_trainer(g, part, dims, cfg, mesh))
        for name, cfg in MODES_4.items()}

    # the wire: every round of this plan is a full permutation, so the
    # permutes of one shard's compiled step move 1/4 of the scheduled bytes
    from repro.analysis.hlo import hlo_census

    def wire(config, fused):
        tr = _trainer(g, part, dims, config, mesh)
        census = hlo_census(tr._step.lower(tr.state).compile().as_text())
        return {
            "full_rounds": all(len(r.pairs) == 4 for r in tr._plan.rounds),
            "permute_bytes":
                census.collectives["collective-permute"]["bytes"],
            "wire_bytes": tr.comm_stats["wire_bytes"],
            "plan_bytes": messages.exchange_bytes(
                tr._plan, gathered_widths(dims, fused=fused))["wire_bytes"],
            "z0_bytes": messages.exchange_bytes(
                tr._plan, dims[:1])["wire_bytes"],
        }

    out["wire"] = wire(TrainerConfig.packed(), False)
    out["wire-fused"] = wire(TrainerConfig.packed(fused=True), True)
    print(json.dumps(out))


@pytest.fixture(scope="module")
def four_shards():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", sorted(MODES_4))
def test_round_matches_wide_first_serial_on_4_shards(four_shards, mode):
    _check(four_shards[mode])


def test_wire_accounting_matches_the_compiled_permutes(four_shards):
    wire = four_shards["wire"]
    assert wire["full_rounds"], wire
    assert wire["wire_bytes"] == wire["plan_bytes"], wire
    assert 4 * wire["permute_bytes"] == wire["wire_bytes"], wire


def test_fused_wire_adds_z0_and_matches_the_compiled_permutes(four_shards):
    """The fused wire's one-pass Z sites still gather Z_0: its step moves
    exactly Z_0's payload more than the unfused step, which gathers none."""
    wire, fused = four_shards["wire"], four_shards["wire-fused"]
    assert fused["full_rounds"], fused
    assert fused["wire_bytes"] == fused["plan_bytes"], fused
    assert 4 * fused["permute_bytes"] == fused["wire_bytes"], fused
    assert fused["z0_bytes"] > 0, fused
    assert fused["wire_bytes"] - wire["wire_bytes"] == fused["z0_bytes"], \
        (wire, fused)


# ---------------------------------------------------------------------------
# engagement
# ---------------------------------------------------------------------------

def _trained(dims, config=None):
    g, part = _graph()
    tr = _trainer(g, part, dims, config or TrainerConfig.p2p())
    tr.train(1)
    return tr


# (C1, C2) with C0 = 8 features and 4 classes -> the tally each program
# records: the constructor aggregates Z_0 once; the step aggregates Z_1 and
# the dual's fresh Z_1; an eval layer multiplies Ã Z_0 by W_1 and
# aggregates every later one at min(C_in, C_out)
TALLIES = {
    "photo-like": ((HIDDEN, 4), {"construct": [8], "step": [HIDDEN, HIDDEN],
                                 "metrics": [4, 4], "lagrangian": [4]}),
    "one-layer": ((4,), {"construct": [8], "step": [], "metrics": [],
                         "lagrangian": []}),
    "widening-last": ((2, 4), {"construct": [8], "step": [2, 2],
                               "metrics": [2, 2], "lagrangian": [2]}),
    "all-widening": ((16, 32), {"construct": [8], "step": [16, 16],
                                "metrics": [16, 16], "lagrangian": [16]}),
}


@pytest.mark.parametrize("case", sorted(TALLIES))
def test_aggregation_tally_reads_each_programs_widths(case):
    g, _ = _graph()
    assert (g.features.shape[1], g.num_classes) == (8, 4)
    widths, expected = TALLIES[case]
    tr = _trained((8,) + widths)
    assert tr.comm_stats["aggregations"] == expected


def _aggregation_widths(jaxpr, n_pad) -> list:
    """Output widths of the dot_generals that contract an (n_pad, n_pad)
    adjacency block, in every sub-jaxpr."""
    from repro.analysis.rules.precision import _sub_jaxprs

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                tuple(v.aval.shape[-2:]) == (n_pad, n_pad)
                for v in eqn.invars):
            out.append(int(eqn.outvars[0].aval.shape[-1]))
        for sub in _sub_jaxprs(eqn.params):
            out += _aggregation_widths(sub, n_pad)
    return out


@pytest.mark.parametrize("config", ["dense", "compressed"])
def test_eval_programs_aggregate_no_hidden_width(config):
    g, _ = _graph()
    c0, c2 = g.features.shape[1], g.num_classes
    tr = _trained((c0, HIDDEN, c2), MODES_1[config])
    for prog in (tr._metrics, tr._lagrangian):
        jx = jax.make_jaxpr(prog.fn)(prog.data, tr.state)
        widths = _aggregation_widths(jx.jaxpr, tr.layout.n_pad)
        assert widths and HIDDEN not in widths, widths
        # Ã Z_0 is the constructor's: the first layer is a GEMM on it
        assert set(widths) == {c2}, widths


def _program_widths(tr) -> dict:
    """``_aggregation_widths`` of the step, metrics and Lagrangian jaxprs."""
    n_pad = tr.layout.n_pad
    return {name: _aggregation_widths(
                jax.make_jaxpr(prog.fn)(prog.data, *args).jaxpr, n_pad)
            for name, prog, args in (
                ("step", tr._step, tr._analysis_args),
                ("metrics", tr._metrics, (tr.state,)),
                ("lagrangian", tr._lagrangian, (tr.state,)))}


def _check_no_input_width(widths, fused=False):
    c0 = _graph()[0].features.shape[1]
    # the helper sees each program's aggregations: Z_1's in the step, the
    # 4-lane ones of the eval programs
    assert HIDDEN in widths["step"], widths
    assert widths["metrics"] and widths["lagrangian"], widths
    assert c0 not in widths["metrics"] + widths["lagrangian"], widths
    # Ã Z_0 comes from the constructor; only the fused wire's one-pass
    # layer-1 Z target may aggregate Z_0 (its CPU oracle forms Ã(Z_0 W_1)
    # at C_1, so it holds none here)
    assert widths["step"].count(c0) <= (1 if fused else 0), widths


@pytest.mark.parametrize("mode", sorted(MODES_1))
def test_programs_aggregate_no_input_width(mode):
    g, part = _graph()
    dims = (g.features.shape[1], HIDDEN, g.num_classes)
    kops.repro_force_interpret(mode.endswith("interpret"))
    try:
        widths = _program_widths(_trainer(g, part, dims, MODES_1[mode]))
    finally:
        kops.repro_force_interpret(False)
    _check_no_input_width(widths)


@pytest.mark.parametrize("mode", sorted(MODES_4))
def test_programs_aggregate_no_input_width_on_4_shards(four_shards, mode):
    _check_no_input_width(four_shards["widths"][mode],
                          fused=mode.startswith("fused"))


# how each one-shard trainer aggregates Z_0: "kernel" dispatches
# kops.community_spmm_ell (the jnp oracle off the chip), "interpret" runs
# the Pallas kernel body, "einsum" and "dense" the step's own einsums
INPUT_AGG_PATHS = {
    "kernel": TrainerConfig.packed(use_kernel=True),
    "interpret": TrainerConfig.packed(use_kernel=True),
    "einsum": TrainerConfig.p2p(),
    "dense": TrainerConfig.dense(),
}


def _step_input_agg(tr):
    """Ã Z_0 as one shard's step aggregated it every round before the
    constructor cached it: the gathered blocked Z_0, masked to the shard's
    neighbour rows, through the step's aggregation for the trainer's mode."""
    d = tr.data
    nbrf = d.neighbor_mask.astype(jnp.float32)
    z0 = jnp.asarray(tr.layout.pack(tr.graph.features))
    zh = z0 * jnp.max(nbrf, axis=0)[:, None, None]
    if not tr.compressed:
        return jnp.einsum("kmip,mpc->kic",
                          d.a_blocks * nbrf[:, :, None, None], zh)
    if tr.config.use_kernel:
        return kops.community_spmm_ell(d.ell_blocks, d.ell_indices,
                                       d.ell_mask, zh, d.row_counts,
                                       d.nbr_counts)
    zg = zh[d.ell_indices] * d.ell_mask.astype(jnp.float32)[..., None, None]
    return jnp.einsum("kdip,kdpc->kic", d.ell_blocks.astype(jnp.float32),
                      zg)


@pytest.mark.parametrize("path", sorted(INPUT_AGG_PATHS))
def test_input_aggregate_is_the_steps_aggregation_of_z0(path):
    g, part = _graph()
    dims = (g.features.shape[1], HIDDEN, g.num_classes)
    kops.repro_force_interpret(path == "interpret")
    try:
        tr = _trainer(g, part, dims, INPUT_AGG_PATHS[path])
        want = np.asarray(jax.jit(lambda: _step_input_agg(tr))())
    finally:
        kops.repro_force_interpret(False)
    ax = np.asarray(tr.ax)
    assert tr.ax.sharding.spec == jax.sharding.PartitionSpec(AXIS)
    assert ax.shape == (tr.data.num_parts, tr.layout.n_pad, dims[0])
    assert np.abs(ax).max() > 0
    if path in ("kernel", "interpret"):
        np.testing.assert_array_equal(ax, want)
    else:
        # the step's einsum and the eval programs' differ in their masks
        # and contraction order: float32 rounding of a few dozen terms
        np.testing.assert_allclose(ax, want, rtol=1e-6, atol=1e-6)


if __name__ == "__main__":
    _four_shard_main()
