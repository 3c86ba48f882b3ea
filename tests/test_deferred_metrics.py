"""``ParallelADMMTrainer.train()`` reads its metrics once per call.

The rounds are dispatched back to back and every round's four scalars
come back in one transfer at the end of the call.  The same programs run
on the same states in the same order as a loop that waits for each step
and reads each scalar with ``float()``, so the log and the iterates must
equal that loop's bit for bit.
"""
import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import gcn, graph
from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
from repro.core.serial import TrainLog
from repro.core.subproblems import ADMMConfig

CASES = {
    "2-layer": ((8,), TrainerConfig.packed()),
    "3-layer": ((8, 8), TrainerConfig.packed()),
    "minibatch": ((8,), TrainerConfig.minibatch(batch_fraction=1.0)),
}


def _trainer(case):
    hidden, config = CASES[case]
    g, part = graph.synthetic_powerlaw_communities(
        num_parts=4, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.8)
    mesh = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,))
    cfg = gcn.GCNConfig(layer_dims=(8, *hidden, g.num_classes))
    return ParallelADMMTrainer(cfg, ADMMConfig(nu=1e-3, rho=1e-3), g,
                               num_parts=int(part.max()) + 1, seed=0,
                               part=part, mesh=mesh, config=config)


def _round_by_round(t, epochs):
    """One round at a time: the step, a wait for it, both eval programs
    and four ``float()`` reads before the next step is dispatched."""
    log = TrainLog()
    for epoch in range(epochs):
        t.step()
        jax.block_until_ready(t.state.zs[-1])
        tr, te, res = t._metrics(t.state)
        lag = t._lagrangian(t.state)
        log.epoch.append(epoch)
        log.train_acc.append(float(tr))
        log.test_acc.append(float(te))
        log.lagrangian.append(float(lag))
        log.residual.append(float(res))
    return log


def _values(log):
    return [log.train_acc, log.test_acc, log.lagrangian, log.residual]


def _assert_same_state(a, b):
    a, b = jax.device_get(a.state), jax.device_get(b.state)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", CASES)
def test_train_logs_and_leaves_what_a_round_by_round_loop_does(case):
    ours, theirs = _trainer(case), _trainer(case)
    log = ours.train(4)
    ref = _round_by_round(theirs, 4)
    assert log.epoch == ref.epoch == [0, 1, 2, 3]
    assert _values(log) == _values(ref)
    assert all(np.isfinite(v) for v in log.lagrangian)
    _assert_same_state(ours, theirs)


@pytest.mark.parametrize("case", ("2-layer", "3-layer"))
def test_calls_split_anywhere_log_the_same_rounds(case):
    split, whole = _trainer(case), _trainer(case)
    a, b = split.train(3), split.train(2)
    log = whole.train(5)
    assert a.epoch + b.epoch == [0, 1, 2, 0, 1]
    assert [x + y for x, y in zip(_values(a), _values(b))] == _values(log)
    _assert_same_state(split, whole)


def test_epoch_time_is_the_calls_mean_per_round():
    t = _trainer("2-layer")
    log = t.train(3)
    assert len(log.epoch_time_s) == 3
    assert len(set(log.epoch_time_s)) == 1 and log.epoch_time_s[0] > 0
    assert t.train(0).as_dict() == TrainLog().as_dict()
