"""TrainerConfig: the one home of the trainer's options.

``TrainerConfig()`` is the trainer's one path; the matrix test pins the
range checks ``__post_init__`` raises, with their exact messages, and the
presets resolve to plain configs.
"""
import argparse
import dataclasses
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


def _trainer(config=None):
    g, part = _graph()
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    return ParallelADMMTrainer(cfg, admm, g, num_parts=4, seed=0,
                               part=part, mesh=mesh, config=config)


# ---------------------------------------------------------------------------
# the validation matrix: every range check, with the exact message
# ---------------------------------------------------------------------------

INVALID = [
    (dict(batch_fraction=0.0),
     "batch_fraction must be in (0, 1], got 0.0"),
    (dict(batch_fraction=1.5),
     "batch_fraction must be in (0, 1], got 1.5"),
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


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------

def test_presets():
    # ``packed`` names the one path: a plain config with its overrides
    assert TrainerConfig.packed() == TrainerConfig()
    mb = TrainerConfig.minibatch()
    assert mb == TrainerConfig(batch_fraction=0.25)
    assert TrainerConfig.minibatch(batch_fraction=0.5).batch_fraction == 0.5
    assert TrainerConfig.packed(comm_bf16=True).comm_bf16 is True
    # overlap composes with sampling (per-sub-plan arrival groups) and
    # with the fused kernel
    ov = TrainerConfig.minibatch(batch_fraction=0.5, overlap=True)
    assert ov.overlap and ov.batch_fraction == 0.5
    fu = TrainerConfig.packed(fused=True, overlap=True)
    assert fu.fused and fu.overlap
    # the deleted design switches are not options any more
    names = {f.name for f in dataclasses.fields(TrainerConfig)}
    assert not names & {"compressed", "transport", "packed", "pad_mode"}
    with pytest.raises(TypeError):
        TrainerConfig(compressed=True)


def test_config_is_frozen():
    cfg = TrainerConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.fused = True


def test_from_cli_args_reads_dest_names():
    ns = argparse.Namespace(overlap=True, fused=True,
                            batch_fraction=0.5, stale_decay=0.75,
                            sample_seed=3, unrelated="ignored")
    cfg = TrainerConfig.from_cli_args(ns)
    assert cfg == TrainerConfig(overlap=True, fused=True,
                                batch_fraction=0.5, stale_decay=0.75,
                                sample_seed=3)
    # missing attributes keep field defaults
    assert TrainerConfig.from_cli_args(argparse.Namespace()) \
        == TrainerConfig()


def test_default_construction_warns_nothing():
    """``TrainerConfig()`` builds the production path: packed planes, the
    ELL store and the row-exact plan, with no warning."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        tr = _trainer()
    assert tr.config == TrainerConfig()
    assert tr.comm_stats["minibatch"] == {"enabled": False}
    assert tr.state.zs[0].shape[0] == tr.packed_layout.total_rows
    assert tr.data.ell_blocks.ndim == 4
    assert tr._plan.row_exact and tr.layout.pad_mode == "bucketed"
    # the flag kwargs of the old shim are gone
    with pytest.raises(TypeError):
        ParallelADMMTrainer(tr.cfg, tr.admm, tr.graph, num_parts=4,
                            compressed=True)
