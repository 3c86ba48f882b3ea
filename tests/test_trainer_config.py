"""TrainerConfig: the one home of every trainer mode flag.

The matrix test pins the contract the API redesign promised: every
invalid flag combination the old ``ParallelADMMTrainer.__init__`` inline
checks rejected still raises — from ``TrainerConfig.__post_init__`` now —
with the *identical* message, through every construction path (direct
config, presets, the deprecated old-kwargs shim).  The shim itself must
resolve to the same config the explicit path builds and fire a
DeprecationWarning exactly once.
"""
import argparse
import warnings

import jax
import pytest
from jax.sharding import AxisType

from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig


def _graph():
    return graph.synthetic_powerlaw_communities(
        num_parts=4, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.5)


def _trainer(config=None, **kw):
    g, part = _graph()
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    return ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0,
                               part=part, mesh=mesh, config=config, **kw)


# ---------------------------------------------------------------------------
# the validation matrix: every constraint of the historic inline ladder,
# with the exact message it has always raised
# ---------------------------------------------------------------------------

INVALID = [
    (dict(transport="bogus"),
     "unknown transport 'bogus'; expected 'p2p' or 'allgather'"),
    (dict(transport="p2p", compressed=False),
     "transport='p2p' requires compressed=True — the dense Z-coupling "
     "reads all M payload rows"),
    (dict(packed=True, compressed=False),
     "packed=True requires compressed=True — the packed plane is only "
     "routed through ELL offsets, never a dense Z-coupling"),
    (dict(packed=True, compressed=True, transport="allgather"),
     "packed=True requires transport='p2p' — the plane layout exists to "
     "feed the row-exact exchange; an all-gather would re-materialise "
     "the strided (M, n_pad, C) payload"),
    (dict(overlap=True),
     "overlap=True requires packed=True — the staged exchange snapshots "
     "are packed planes"),
    (dict(pad_mode="weird"),
     "unknown pad_mode 'weird'; expected 'global' or 'bucketed'"),
    (dict(adjacency_bf16=True, compressed=False),
     "adjacency_bf16=True requires compressed=True"),
    (dict(compressed=True, packed=True, batch_fraction=0.0),
     "batch_fraction must be in (0, 1], got 0.0"),
    (dict(compressed=True, packed=True, batch_fraction=1.5),
     "batch_fraction must be in (0, 1], got 1.5"),
    (dict(compressed=True, batch_fraction=0.5),
     "batch_fraction requires packed=True — the sampled sweep runs on "
     "the sampled shards' packed planes"),
    (dict(stale_decay=0.0),
     "stale_decay must be in (0, 1], got 0.0"),
    (dict(stale_decay=1.5),
     "stale_decay must be in (0, 1], got 1.5"),
]


@pytest.mark.parametrize("kw,msg", INVALID,
                         ids=[m.split(" — ")[0].split(";")[0]
                              for _, m in INVALID])
def test_invalid_combos_raise_from_config(kw, msg):
    with pytest.raises(ValueError) as e:
        TrainerConfig(**kw)
    assert str(e.value) == msg


@pytest.mark.parametrize(
    "kw,msg", [(k, m) for k, m in INVALID if set(k) <= {
        "transport", "compressed", "packed", "overlap", "pad_mode",
        "adjacency_bf16"}],
    ids=[m.split(" — ")[0].split(";")[0] for k, m in INVALID if set(k) <= {
        "transport", "compressed", "packed", "overlap", "pad_mode",
        "adjacency_bf16"}])
def test_invalid_combos_raise_through_the_shim(kw, msg):
    """The old-kwargs path fails with the same message the inline checks
    produced — validation moved, behaviour did not."""
    with pytest.raises(ValueError) as e, \
            pytest.warns(DeprecationWarning, match="TrainerConfig"):
        _trainer(**kw)
    assert str(e.value) == msg


# ---------------------------------------------------------------------------
# transport resolution + presets
# ---------------------------------------------------------------------------

def test_transport_none_resolution():
    assert TrainerConfig().transport == "allgather"
    assert TrainerConfig(compressed=True).transport == "p2p"


def test_presets():
    d = TrainerConfig.dense()
    assert (d.compressed, d.transport) == (False, "allgather")
    p = TrainerConfig.p2p()
    assert (p.compressed, p.transport, p.packed) == (True, "p2p", False)
    k = TrainerConfig.packed()
    assert (k.compressed, k.transport, k.packed) == (True, "p2p", True)
    mb = TrainerConfig.minibatch()
    assert mb.packed and mb.batch_fraction == 0.25
    assert TrainerConfig.minibatch(batch_fraction=0.5).batch_fraction == 0.5
    # presets accept overrides without re-stating the ladder
    assert TrainerConfig.packed(comm_bf16=True).comm_bf16 is True
    # overlap composes with sampling (per-sub-plan arrival groups) and
    # with the fused kernel; fused without packed stays rejected
    ov = TrainerConfig.minibatch(batch_fraction=0.5, overlap=True)
    assert ov.overlap and ov.batch_fraction == 0.5
    fu = TrainerConfig.packed(fused=True, overlap=True)
    assert fu.fused and fu.overlap


def test_config_is_frozen():
    import dataclasses
    cfg = TrainerConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.compressed = True


def test_from_cli_args_reads_dest_names():
    ns = argparse.Namespace(compressed=True, transport="p2p",
                            pad_mode="bucketed", packed=True,
                            batch_fraction=0.5, stale_decay=0.75,
                            sample_seed=3, unrelated="ignored")
    cfg = TrainerConfig.from_cli_args(ns)
    assert cfg == TrainerConfig(compressed=True, transport="p2p",
                                packed=True, batch_fraction=0.5,
                                stale_decay=0.75, sample_seed=3)
    # missing attributes keep field defaults
    assert TrainerConfig.from_cli_args(argparse.Namespace()) \
        == TrainerConfig()


# ---------------------------------------------------------------------------
# the deprecation shim
# ---------------------------------------------------------------------------

def test_shim_resolves_to_the_same_config_and_warns():
    with pytest.warns(DeprecationWarning, match="TrainerConfig"):
        old = _trainer(compressed=True, transport="p2p", packed=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        new = _trainer(config=TrainerConfig.packed())  # no warning
    assert old.config == new.config == TrainerConfig.packed()
    # resolved trainer attributes agree too
    for attr in ("compressed", "transport", "packed", "overlap",
                 "pad_mode"):
        assert getattr(old, attr) == getattr(new, attr)


def test_shim_rejects_config_plus_legacy_and_unknown_kwargs():
    with pytest.raises(ValueError, match="not both"):
        _trainer(config=TrainerConfig(), compressed=True)
    with pytest.raises(TypeError, match="unexpected keyword"):
        _trainer(bogus_flag=True)


def test_default_construction_warns_nothing():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        tr = _trainer()
    assert tr.config == TrainerConfig()
    assert tr.comm_stats["minibatch"] == {"enabled": False}
