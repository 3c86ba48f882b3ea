"""Narrow-first aggregation in the eval programs: where an Ã·Z·W
composite of the metrics or Lagrangian program narrows the width, it is
computed as Ã·(Z·W).  The round's dual update keeps (Ã Z⁺) W⁺, the
association the next W_L line search forms its residual with.

Parity: after one round from a shared state, the trainer's U and its
metrics and Lagrangian match the wide-first (Ã Z) W of the serial
trainer and ``subproblems`` on the same iterates, in every mode, at a
tolerance set by float32 rounding of the reordered sums.

Engagement: ``comm_stats["aggregations"]`` records the width of each
distinct aggregation a program traces; the eval programs' jaxprs hold no
aggregation at the hidden width; a layer that widens keeps wide-first;
the wire accounting prices the gathers as the compiled step's permutes
move them.

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

    # the wire: every round of this plan is a full permutation, so the
    # permutes of one shard's compiled step move 1/4 of the scheduled bytes
    from repro.analysis.hlo import hlo_census
    g, part = _graph()
    dims = (g.features.shape[1], HIDDEN, g.num_classes)
    tr = _trainer(g, part, dims, TrainerConfig.packed(), mesh)
    census = hlo_census(tr._step.lower(tr.state).compile().as_text())
    out["wire"] = {
        "full_rounds": all(len(r.pairs) == 4 for r in tr._plan.rounds),
        "permute_bytes": census.collectives["collective-permute"]["bytes"],
        "wire_bytes": tr.comm_stats["wire_bytes"],
        "plan_bytes": messages.exchange_bytes(
            tr._plan, gathered_widths(dims))["wire_bytes"],
    }
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


# ---------------------------------------------------------------------------
# engagement
# ---------------------------------------------------------------------------

def _trained(dims, config=None):
    g, part = _graph()
    tr = _trainer(g, part, dims, config or TrainerConfig.p2p())
    tr.train(1)
    return tr


# (C1, C2) with C0 = 8 features and 4 classes -> the tally each program
# records: the step aggregates Z_0, Z_1 and the dual's fresh Z_1; an eval
# layer aggregates at min(C_in, C_out)
TALLIES = {
    "photo-like": ((HIDDEN, 4), {"step": [8, HIDDEN, HIDDEN],
                                 "metrics": [8, 4, 4],
                                 "lagrangian": [8, 4]}),
    "one-layer": ((4,), {"step": [8], "metrics": [4], "lagrangian": [4]}),
    "widening-last": ((2, 4), {"step": [8, 2, 2], "metrics": [2, 2, 2],
                               "lagrangian": [2, 2]}),
    "all-widening": ((16, 32), {"step": [8, 16, 16],
                                "metrics": [8, 16, 16],
                                "lagrangian": [8, 16]}),
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
        assert set(widths) == {c0, c2}, widths


if __name__ == "__main__":
    _four_shard_main()
