"""Layerwise (blockwise) ADMM on transformer stacks — the paper's technique
generalized beyond GCN (DESIGN.md §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.layerwise import LayerwiseADMMTrainer
from repro.core.subproblems import ADMMConfig


def _batch(cfg, b=4, s=32, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)),
        "targets": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)),
    }


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-7b", "gemma-2b", "mamba2-1.3b"])
def test_layerwise_admm_decreases_ce(arch):
    cfg = get_config(arch, reduced=True)
    tr = LayerwiseADMMTrainer(cfg, ADMMConfig(nu=1e-2, rho=1e-2))
    batch = _batch(cfg)
    state, z0 = tr.init(jax.random.key(0), batch)
    ce0, _ = tr.metrics(state, z0, batch["targets"])
    it = jax.jit(lambda s: tr.iteration(s, z0, batch["targets"]))
    for _ in range(6):
        state = it(state)
    ce, res = tr.metrics(state, z0, batch["targets"])
    assert float(ce) < 0.7 * float(ce0), (arch, float(ce0), float(ce))
    assert np.isfinite(float(res))


@pytest.mark.slow
def test_layerwise_admm_moe():
    cfg = get_config("deepseek-moe-16b", reduced=True)
    tr = LayerwiseADMMTrainer(cfg, ADMMConfig(nu=1e-2, rho=1e-2))
    batch = _batch(cfg)
    state, z0 = tr.init(jax.random.key(0), batch)
    ce0, _ = tr.metrics(state, z0, batch["targets"])
    it = jax.jit(lambda s: tr.iteration(s, z0, batch["targets"]))
    for _ in range(5):
        state = it(state)
    ce, _ = tr.metrics(state, z0, batch["targets"])
    assert float(ce) < float(ce0)


def test_layerwise_admm_init_satisfies_constraints():
    """Z init from the forward pass => residual ~0 (as in the GCN core)."""
    cfg = get_config("gemma-2b", reduced=True)
    tr = LayerwiseADMMTrainer(cfg, ADMMConfig())
    batch = _batch(cfg)
    state, z0 = tr.init(jax.random.key(0), batch)
    _, res = tr.metrics(state, z0, batch["targets"])
    assert float(res) < 1e-4


def test_layerwise_admm_sharded_runs():
    """Layer axis over 'model', batch over 'data' — the ADMM-as-sharding
    mapping lowers and runs on a host mesh."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])
    cfg = get_config("qwen2-7b", reduced=True)
    tr = LayerwiseADMMTrainer(cfg, ADMMConfig(nu=1e-2, rho=1e-2), mesh=mesh)
    batch = _batch(cfg)
    with mesh:
        state, z0 = tr.init(jax.random.key(0), batch)
        ce0, _ = tr.metrics(state, z0, batch["targets"])
        it = jax.jit(lambda s: tr.iteration(s, z0, batch["targets"]))
        for _ in range(4):
            state = it(state)
        ce, _ = tr.metrics(state, z0, batch["targets"])
    assert float(ce) < float(ce0)
