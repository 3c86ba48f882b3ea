"""Expert-parallel all-to-all MoE dispatch (§Perf pair-2 iterations 4-7):
bit-equivalence with the portable path on a real host mesh, and correct
gating (portable path inside manual regions / without hints)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_config
from repro.models import moe as moe_lib
from repro.sharding.hints import sharding_hints


@pytest.fixture(scope="module")
def setup():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    cfg = get_config("deepseek-moe-16b", reduced=True)
    p = moe_lib.init_moe(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 32, cfg.d_model))
                    .astype(np.float32) * 0.5)
    mesh = jax.make_mesh((2, 2), ("data", "model"), (AxisType.Auto,) * 2, devices=jax.devices()[:4])
    return cfg, p, x, mesh


def test_a2a_matches_portable(setup):
    cfg, p, x, mesh = setup
    base, aux_b = jax.jit(lambda p, x: moe_lib.apply_moe(cfg, p, x))(p, x)
    with mesh, sharding_hints(mesh, moe_a2a=True):
        a2a, aux_a = jax.jit(lambda p, x: moe_lib.apply_moe(cfg, p, x))(p, x)
    np.testing.assert_allclose(np.asarray(base), np.asarray(a2a),
                               rtol=1e-5, atol=1e-5)
    # aux differs only through per-shard capacity rounding
    assert abs(float(aux_b) - float(aux_a)) < 1e-4


def test_a2a_gated_off_without_hints(setup):
    cfg, p, x, mesh = setup
    # no hints context: portable path (no shard_map in the jaxpr)
    jaxpr = jax.make_jaxpr(lambda p, x: moe_lib.apply_moe(cfg, p, x))(p, x)
    assert "shard_map" not in str(jaxpr)


def test_a2a_gated_off_inside_manual_region(setup):
    """Inside an enclosing shard_map (deferred train step) the a2a path
    must defer to the portable dispatch instead of nesting shard_maps."""
    from jax.sharding import PartitionSpec as P
    cfg, p, x, mesh = setup

    def body(xs):
        out, _ = moe_lib.apply_moe(cfg, p, xs)
        return out

    with mesh, sharding_hints(mesh, moe_a2a=True):
        fn = jax.shard_map(body, mesh=mesh, in_specs=P("data", None, None),
                           out_specs=P("data", None, None), check_vma=False,
                           axis_names=frozenset({"data"}))
        out = jax.jit(fn)(x)          # would raise on nested manual axes
    assert np.isfinite(np.asarray(out)).all()


def test_a2a_train_step_deferred_composes(setup):
    """End-to-end: the deferred train step on an MoE arch with hints+a2a
    enabled lowers and runs (a2a gated off inside, hints filtered)."""
    cfg, _, _, mesh = setup
    from repro.models.build import make_model
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    opt_state = model.init_optimizer().init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16))
                                   .astype(np.int32)),
             "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16))
                                    .astype(np.int32))}
    with mesh, sharding_hints(mesh, moe_a2a=True):
        step = jax.jit(lambda p, o, b: model.train_step_deferred(
            mesh, p, o, b))
        params2, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
