"""Packed ragged device state: the Σ-bucket-rows resident plane, the
offset-indexed exchange/aggregation path, and the double-buffered
exchange/aggregation overlap.

The invariant under test everywhere: packing changes where rows LIVE,
never the math — the packed trainer's iterates are *bitwise* equal to the
strided (M, n_pad, ...) path's on CPU (the zero-outside-counts contract
makes pack/unpack lossless and the einsum oracles see identical operands),
while ``comm_stats['state']`` shows resident rows/bytes dropping.  The
overlap mode re-associates the neighbour sum by arrival round, so its
parity is tolerance- rather than bit-level; its wire schedule is
byte-identical and ``comm_stats['overlap']`` prices what stays exposed.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import analysis
from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer
from repro.core.subproblems import ADMMConfig


def _skewed(m=8, seed=0, skew=0.8):
    return graph.synthetic_powerlaw_communities(
        num_parts=m, nodes_per_part=12, attach=1, seed=seed, feat_dim=8,
        size_skew=skew)


def _trainer(g, part, mesh, **kw):
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    m = int(part.max()) + 1
    kw.setdefault("compressed", True)
    return ParallelADMMTrainer(cfg, admm, g, num_parts=m, seed=0,
                               part=part, mesh=mesh,
                               pad_mode="bucketed", **kw)


# ---------------------------------------------------------------------------
# device layout geometry
# ---------------------------------------------------------------------------

def test_device_layout_matches_plan_geometry():
    """The device layout and the exchange plan derive local offsets and
    plane heights from the same bucket counts — a shard's send plane IS
    its resident state plane, no re-staging between them."""
    from repro.core import messages
    g, part = _skewed()
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    dl = layout.device_layout(4)
    plan = messages.build_neighbor_exchange(
        layout.neighbor_mask, 4, layout.n_pad, sizes=layout.sizes,
        row_counts=layout.eff_row_counts())
    assert plan.plane_rows == dl.plane_rows
    np.testing.assert_array_equal(plan.local_offsets, dl.local_offsets)
    np.testing.assert_array_equal(plan.row_counts, dl.row_counts)
    # skew actually bites: the packed stack is strictly shorter
    assert dl.total_rows < 8 * layout.n_pad


def test_global_unpack_rows_is_the_scatter_inverse():
    g, part = _skewed()
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    dl = layout.device_layout(2)
    rng = np.random.default_rng(0)
    blocked = layout.pack(
        rng.normal(size=(g.num_nodes, 3)).astype(np.float32))
    packed = dl.pack_state(blocked)
    # the (M·n_pad,) gather table reproduces unpack_state via take-fill
    idx = dl.global_unpack_rows()
    padded = np.concatenate([packed, np.zeros((1, 3), np.float32)])
    via_table = padded[np.minimum(idx, dl.total_rows)].reshape(
        dl.num_parts, layout.n_pad, 3)
    np.testing.assert_array_equal(via_table, dl.unpack_state(packed))
    assert dl.state_rows() == dl.total_rows
    assert dl.state_rows(strided=True) == dl.num_parts * layout.n_pad


# ---------------------------------------------------------------------------
# trainer validation + comm_stats accounting
# ---------------------------------------------------------------------------

def test_packed_flag_validation():
    g, part = _skewed()
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    with pytest.raises(ValueError, match="compressed"):
        _trainer(g, part, mesh, packed=True, compressed=False)
    with pytest.raises(ValueError, match="p2p"):
        _trainer(g, part, mesh, packed=True, transport="allgather")
    with pytest.raises(ValueError, match="packed"):
        _trainer(g, part, mesh, overlap=True)


def test_comm_stats_state_accounting():
    g, part = _skewed()
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    tr = _trainer(g, part, mesh, packed=True)
    st = tr.comm_stats["state"]
    assert st["packed"] is True
    assert st["node_rows"] <= st["bucket_rows"] <= st["rows"] \
        <= st["strided_rows"]
    assert st["rows"] < st["strided_rows"]          # the skew pays off
    assert st["z_bytes"] < st["z_strided_bytes"]
    assert st["resident_bytes"] < st["strided_equiv_bytes"]
    # the strided trainer reports the same schema with packed=False and
    # rows at the full M·n_pad stride
    ref = _trainer(g, part, mesh).comm_stats["state"]
    assert ref["packed"] is False
    assert ref["rows"] == ref["strided_rows"] == st["strided_rows"]


# ---------------------------------------------------------------------------
# single-shard bitwise parity (the multi-shard run is the subprocess below)
# ---------------------------------------------------------------------------

def test_packed_trainer_bitwise_matches_strided_one_shard():
    """On one shard the packed trainer stores Z/U as packed planes but
    runs the identical blocked math — every iterate, the Lagrangian and
    the metrics must match the strided trainer BITWISE."""
    g, part = _skewed()
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    ref = _trainer(g, part, mesh)
    pk = _trainer(g, part, mesh, packed=True)
    dl = pk.packed_layout
    assert dl is not None
    for _ in range(4):
        ref.step()
        pk.step()
    for zr, zp in zip(ref.state.zs, pk.state.zs):
        assert zp.shape[0] == dl.total_rows
        np.testing.assert_array_equal(np.asarray(zr),
                                      dl.unpack_state(np.asarray(zp)))
    np.testing.assert_array_equal(np.asarray(ref.state.u),
                                  dl.unpack_state(np.asarray(pk.state.u)))
    for wr, wp in zip(ref.state.weights, pk.state.weights):
        np.testing.assert_array_equal(np.asarray(wr), np.asarray(wp))
    assert float(ref._lagrangian(ref.state)) == \
        float(pk._lagrangian(pk.state))
    for a, b in zip(ref._metrics(ref.state), pk._metrics(pk.state)):
        assert float(a) == float(b)


# ---------------------------------------------------------------------------
# the packed-resident-state analysis rule
# ---------------------------------------------------------------------------

def _hlo(body: str) -> str:
    return ("HloModule test\n\n"
            "ENTRY %main (p0: f32[8,8]) -> f32[8,8] {\n"
            + body + "\n}\n")


def test_packed_resident_state_rule_fires_on_blocked_stacks():
    exp = {"n_pad": 16, "state_packed": True, "packed_rows_bound": 4}
    # a computed (8, 16, 7) blocked row stack: 8 rows > r_pad = 4
    text = _hlo(
        "  %p0 = f32[8,16,7]{2,1,0} parameter(0)\n"
        "  ROOT %b = f32[8,16,7]{2,1,0} negate(f32[8,16,7]{2,1,0} %p0)")
    rep = analysis.analyze_hlo(text, expectations=exp)
    hits = rep.findings_for("memory/packed-resident-state")
    assert len(hits) == 1 and hits[0].location == "b"
    assert hits[0].severity.name == "ERROR"
    # parameters may hold the closed-over blocked store
    assert not any(f.location == "p0" for f in hits)
    # within the receive-view bound: silent
    ok = _hlo(
        "  %p0 = f32[4,16,7]{2,1,0} parameter(0)\n"
        "  ROOT %b = f32[4,16,7]{2,1,0} negate(f32[4,16,7]{2,1,0} %p0)")
    assert not analysis.analyze_hlo(ok, expectations=exp).findings_for(
        "memory/packed-resident-state")
    # (rows, n_pad, n_pad) is an adjacency block stack — the dense-
    # adjacency rule's turf, not this one's
    adj = _hlo(
        "  %p0 = f32[8,16,16]{2,1,0} parameter(0)\n"
        "  ROOT %b = f32[8,16,16]{2,1,0} negate(f32[8,16,16]{2,1,0} %p0)")
    assert not analysis.analyze_hlo(adj, expectations=exp).findings_for(
        "memory/packed-resident-state")
    # unpacked configs are out of scope
    off = analysis.analyze_hlo(
        text, expectations=dict(exp, state_packed=False))
    assert not off.findings_for("memory/packed-resident-state")


# ---------------------------------------------------------------------------
# 4-shard subprocess: packed p2p vs strided bitwise, overlap tolerance,
# and the compiled-program proof (analysis rules over the real HLO)
# ---------------------------------------------------------------------------

_PACKED_WORKER = r"""
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
    num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
    size_skew=0.8)
cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
admm = ADMMConfig(nu=1e-3, rho=1e-3)
mesh = jax.make_mesh((N_SHARDS,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:N_SHARDS])

def build(**kw):
    return ParallelADMMTrainer(cfg, admm, g, num_parts=8, seed=0,
                               part=part, mesh=mesh, compressed=True,
                               pad_mode="bucketed", **kw)

serial = SerialADMMTrainer(cfg, admm, g, seed=0)
ref = build()
pk = build(packed=True)
ov = build(packed=True, overlap=True)
dl = pk.packed_layout

# resident-state accounting: packed planes strictly undercut the stride
st = pk.comm_stats["state"]
assert st["packed"] and st["rows"] < st["strided_rows"], st
assert st["z_bytes"] < st["z_strided_bytes"], st
# wire schedule identical either way; overlap prices the exposed share
assert pk.comm_stats["wire_bytes"] == ref.comm_stats["wire_bytes"]
assert not pk.comm_stats["overlap"]["enabled"]
ost = ov.comm_stats["overlap"]
assert ost["enabled"] and ost["overlap_efficiency"] > 0, ost
assert ost["exposed_wire_s"] < ost["total_wire_s"], ost
print("STATS_OK")

for _ in range(3):
    serial.step(); ref.step(); pk.step(); ov.step()

# packed p2p == strided p2p BITWISE (pack/unpack is lossless and the
# math never sees the relocation)
for zr, zp in zip(ref.state.zs, pk.state.zs):
    np.testing.assert_array_equal(np.asarray(zr),
                                  dl.unpack_state(np.asarray(zp)))
np.testing.assert_array_equal(np.asarray(ref.state.u),
                              dl.unpack_state(np.asarray(pk.state.u)))
for wr, wp in zip(ref.state.weights, pk.state.weights):
    np.testing.assert_array_equal(np.asarray(wr), np.asarray(wp))
assert float(ref._lagrangian(ref.state)) == float(pk._lagrangian(pk.state))
print("PACKED_BITWISE_OK")

# overlap re-associates the neighbour sum by arrival group: tolerance
for zp, zo in zip(pk.state.zs, ov.state.zs):
    np.testing.assert_allclose(np.asarray(zp), np.asarray(zo),
                               rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(np.asarray(pk.state.u), np.asarray(ov.state.u),
                           rtol=2e-4, atol=2e-5)
lp, lo = float(pk._lagrangian(pk.state)), float(ov._lagrangian(ov.state))
assert abs(lp - lo) <= 1e-4 * max(1.0, abs(lp)), (lp, lo)
print("OVERLAP_OK")

# both packed trainers reproduce the SERIAL trainer's W/Z/U + Lagrangian
lag_s = float(serial._lagr(serial.a_tilde, serial.z0, serial.labels,
                           serial.train_mask, serial.state))
for tr in (pk, ov):
    for zs_, zp in zip(serial.state.zs, tr.state.zs):
        np.testing.assert_allclose(
            np.asarray(zs_),
            tr.layout.unpack(dl.unpack_state(np.asarray(zp))),
            rtol=2e-3, atol=2e-4)
    for ws, wp in zip(serial.state.weights, tr.state.weights):
        np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                                   rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(serial.state.u),
        tr.layout.unpack(dl.unpack_state(np.asarray(tr.state.u))),
        rtol=2e-3, atol=2e-4)
    lag_t = float(tr._lagrangian(tr.state))
    assert abs(lag_s - lag_t) <= 1e-4 * max(1.0, abs(lag_s)), (lag_s, lag_t)
print("SERIAL_PARITY_OK")

# compiled-program proof: the packed step holds no blocked row stack
# taller than r_pad, keeps the gather-free p2p schedule, and the 8-row
# ELL tile quantum is the only alignment deviation (warning, waived)
from repro import analysis
for tr, name in ((pk, "packed"), (ov, "packed-overlap")):
    rep = analysis.analyze_trainer(tr, config=name)
    assert analysis.no_findings(rep, rule="memory/packed-resident-state")
    assert analysis.no_findings(rep,
                                rule="collective/no-allgather-under-p2p")
    assert not rep.errors(), rep.summary()
print("HLO_OK")
"""


def test_packed_p2p_matches_strided_on_4_shards():
    """The acceptance run: a 4-shard packed trainer on the size-skewed
    graph matches the strided trainer's W/Z/U and Lagrangian BITWISE
    after 3 iterations, the overlap trainer matches to tolerance, the
    resident state strictly undercuts the stride, and the compiled step
    passes the packed-resident-state rule."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _PACKED_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("STATS_OK", "PACKED_BITWISE_OK", "OVERLAP_OK",
                "SERIAL_PARITY_OK", "HLO_OK"):
        assert tag in out.stdout, out.stdout


def test_gather_tables_are_memoized():
    """The static pack/unpack gather tables are built once and reused —
    the serving engine and the trainer hot path re-read them every call."""
    g, part = _skewed()
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    dl = layout.device_layout(2)
    assert dl.global_unpack_rows() is dl.global_unpack_rows()
    assert dl.global_pack_rows() is dl.global_pack_rows()
    # memoization must not leak across instances
    dl2 = layout.device_layout(2)
    assert dl2.global_unpack_rows() is not dl.global_unpack_rows()
    np.testing.assert_array_equal(dl2.global_unpack_rows(),
                                  dl.global_unpack_rows())
