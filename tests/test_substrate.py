"""Substrate unit tests: optimizers, checkpointing, data pipeline, sharding
rules, HLO census."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import optimizers


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,lr", [("sgd", 0.1), ("momentum", 0.05),
                                     ("adam", 0.05), ("adagrad", 0.3),
                                     ("adadelta", 2.0)])
def test_optimizers_minimize_quadratic(name, lr):
    opt = optimizers.make(name, lr)
    x = {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(1.5)}
    state = opt.init(x)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    for _ in range(200):
        g = jax.grad(loss)(x)
        upd, state = opt.update(g, state, x)
        x = jax.tree.map(lambda a, u: a + u, x, upd)
    assert float(loss(x)) < 0.05, (name, float(loss(x)))


def test_adam_moments_are_f32_for_bf16_params():
    opt = optimizers.make("adam", 1e-3)
    params = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
    state = opt.init(params)
    assert state["m"]["w"].dtype == jnp.float32
    g = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    upd, state = opt.update(g, state, params)
    assert upd["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro import checkpoint as ckpt
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.asarray([1, 2, 3], jnp.int32)},
            "scalar": jnp.asarray(2.5)}
    ckpt.save(tmp_path, tree, step=7)
    like = jax.tree.map(lambda l: jnp.zeros_like(l), tree)
    restored = ckpt.restore(tmp_path, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_mismatch(tmp_path):
    from repro import checkpoint as ckpt
    tree = {"a": jnp.ones((2,))}
    ckpt.save(tmp_path, tree, step=1)
    ckpt.save(tmp_path, tree, step=5)
    assert ckpt.latest_step(tmp_path) == 5
    with pytest.raises(ValueError):
        ckpt.restore(tmp_path, {"a": jnp.ones((3,))})


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_token_stream_is_learnable_and_shaped():
    from repro.data import synthetic_token_batches
    it = synthetic_token_batches(vocab_size=97, batch=4, seq_len=32, seed=0)
    b = next(it)
    assert b["tokens"].shape == (4, 32) and b["targets"].shape == (4, 32)
    assert b["tokens"].max() < 97 and b["tokens"].min() >= 0
    # targets are the shifted stream
    b2 = next(it)
    assert not np.array_equal(b["tokens"], b2["tokens"])


def test_pipeline_places_batches():
    from repro.data import TokenPipeline, synthetic_token_batches
    src = synthetic_token_batches(50, 4, 16, seed=1)
    pipe = TokenPipeline(src, mesh=None)
    b = next(pipe)
    assert isinstance(b["tokens"], jax.Array)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_rules():
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.configs import get_config
    from repro.models.build import make_model
    from repro.sharding import partition

    n = len(jax.devices())
    mesh = jax.make_mesh((1, n), ("data", "model"), (AxisType.Auto,) * 2,
                         devices=jax.devices())
    cfg = get_config("deepseek-moe-16b")      # full config, abstract only
    model = make_model(cfg)
    params_s = jax.eval_shape(model.init, jax.random.key(0))
    specs = partition.param_specs(cfg, mesh, params_s)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): spec
            for path, spec in
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    # expert weights: E axis over model
    moe_keys = [k for k in flat if "w_gate" in k]
    assert moe_keys and all(flat[k][1] == "model" for k in moe_keys)
    # norms replicated
    norm_keys = [k for k in flat if "norm" in k and "scale" in k]
    assert norm_keys and all(
        all(s is None for s in flat[k]) for k in norm_keys)
    # embedding vocab over model
    emb = [k for k in flat if k.endswith("table")]
    assert emb and flat[emb[0]][0] == "model"


# ---------------------------------------------------------------------------
# HLO census (roofline source of truth)
# ---------------------------------------------------------------------------

def test_hlo_census_counts_scan_trips():
    from repro.launch.roofline import hlo_census

    def f(params, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, params)
        return c.sum()

    params = jax.ShapeDtypeStruct((5, 16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    hlo = jax.jit(f).lower(params, x).compile().as_text()
    census = hlo_census(hlo)
    assert census.flops == 5 * 2 * 16 ** 3
    assert 5 in census.while_trips


def test_hlo_census_collectives():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    from repro.launch.roofline import hlo_census
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >1 device")
    mesh = jax.make_mesh((n,), ("d",), (AxisType.Auto,), devices=jax.devices())

    def g(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                             in_specs=P("d"), out_specs=P(),
                             check_vma=False)(x)

    x = jax.ShapeDtypeStruct((n, 64), jnp.float32)
    hlo = jax.jit(g).lower(x).compile().as_text()
    census = hlo_census(hlo)
    assert census.collectives["all-reduce"]["count"] >= 1
    assert census.collective_bytes >= 64 * 4


def test_roofline_terms_pick_dominant():
    from repro.launch.roofline import roofline_terms
    t = roofline_terms(flops=197e12, hbm_bytes=1.0, collective_total=1.0)
    assert t["dominant"] == "compute_s"
    t = roofline_terms(flops=1.0, hbm_bytes=819e9 * 5, collective_total=1.0)
    assert t["dominant"] == "memory_s"


# ---------------------------------------------------------------------------
# LR schedules
# ---------------------------------------------------------------------------

def test_schedules():
    from repro.optim import schedules
    cos = schedules.make("cosine", total_steps=100, warmup_steps=10)
    assert float(cos(0)) < float(cos(9)) <= 1.0          # warming up
    assert abs(float(cos(10)) - 1.0) < 0.02              # peak after warmup
    assert float(cos(99)) < 0.15                         # decayed
    warm = schedules.make("warmup", 0, warmup_steps=5)
    assert float(warm(0)) == pytest.approx(0.2)
    assert float(warm(10)) == 1.0
