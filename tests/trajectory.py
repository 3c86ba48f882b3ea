"""Trajectory checks for the multi-shard test workers (they put this
directory on ``PYTHONPATH``): a trainer's W, node-order Z, U and
Lagrangian after the same rounds, against a one-shard trainer on the same
partition or against the serial trainer."""
import jax
import numpy as np

from repro.core.parallel import AXIS, ParallelADMMTrainer


def one_shard_twin(tr, **kw):
    """``tr``'s config on a one-device mesh over the same partition and
    communities: the exchange-free blocked path, where every neighbour row
    is the shard's own.  ``kw`` are the trainer's other arguments."""
    mesh = jax.make_mesh((1,), (AXIS,), (jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:1])
    return ParallelADMMTrainer(tr.cfg, tr.admm, tr.graph, mesh=mesh,
                               config=tr.config, **kw)


def nodes(tr, x):
    """A packed state plane of ``tr`` in node order."""
    return tr.layout.unpack(tr.packed_layout.unpack_state(np.asarray(x)))


def assert_same_trajectory(tr, ref, rtol=2e-4, atol=2e-5, lag_rtol=1e-4):
    """Two parallel trainers hold the same iterates to float32
    re-association of their sums."""
    for a, b in zip(tr.state.weights, ref.state.weights):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)
    for a, b in zip(tr.state.zs, ref.state.zs):
        np.testing.assert_allclose(nodes(tr, a), nodes(ref, b),
                                   rtol=rtol, atol=atol)
    np.testing.assert_allclose(nodes(tr, tr.state.u), nodes(ref, ref.state.u),
                               rtol=rtol, atol=atol)
    lag_t = float(tr._lagrangian(tr.state))
    lag_r = float(ref._lagrangian(ref.state))
    assert abs(lag_t - lag_r) <= lag_rtol * max(1.0, abs(lag_r)), \
        (lag_t, lag_r)


def assert_matches_serial(tr, serial, rtol=2e-3, atol=2e-4, lag_rtol=1e-4):
    """A parallel trainer's iterates against the serial trainer's after
    the same rounds."""
    for ws, wp in zip(serial.state.weights, tr.state.weights):
        np.testing.assert_allclose(np.asarray(ws), np.asarray(wp),
                                   rtol=rtol, atol=atol)
    for zs, zp in zip(serial.state.zs, tr.state.zs):
        np.testing.assert_allclose(np.asarray(zs), nodes(tr, zp),
                                   rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(serial.state.u),
                               nodes(tr, tr.state.u), rtol=rtol, atol=atol)
    lag_s = float(serial._lagr(serial.a_tilde, serial.z0, serial.labels,
                               serial.train_mask, serial.state))
    lag_t = float(tr._lagrangian(tr.state))
    assert abs(lag_s - lag_t) <= lag_rtol * max(1.0, abs(lag_s)), \
        (lag_s, lag_t)
