"""Compile-only checks of the community kernels for a described TPU v5e.

No chip is attached: the TPU compiler is handed a ``v5e:2x2`` topology
description and each kernel is lowered and compiled at the shapes the
trainer and server feed it.  This is what interpret mode cannot show —
Mosaic refuses blocks whose last two dims are neither (8, 128)-aligned
nor the full array dim, and kernels whose VMEM footprint it cannot fit.

Widths:
  * paper — Amazon Photo (7,650 nodes) in M=3 communities, n_pad 2552,
    feature widths 745 and 1000, the 8 classes the metrics and
    Lagrangian programs aggregate Ã(Z W) at, and the 745→1000 / 1000→8
    GEMMs the fused Z-update runs;
  * m32 — the 32-community power-law test graphs, C 16–64.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import community_spmm as cs

# (k, max_deg, n_pad, plane_rows) — one shard hosts every community
PAPER = (3, 3, 2552, 3 * 2552)
M32 = (32, 6, 48, 32 * 48)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


f32, i32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("dims,c", [(PAPER, 745), (PAPER, 1000),
                                    (M32, 16), (M32, 64)])
def test_dense_block_kernel_compiles(one_chip, dims, c):
    _, _, n_pad, _ = dims
    m = dims[0]
    _compile(one_chip, cs.community_spmm,
             ((m, n_pad, n_pad), f32), ((m, n_pad, c), f32), ((m,), i32))


@pytest.mark.parametrize("dims,c", [(PAPER, 745), (PAPER, 1000), (PAPER, 8),
                                    (M32, 16), (M32, 32), (M32, 64)])
@pytest.mark.parametrize("block_dtype", [f32, jnp.bfloat16])
def test_ell_kernel_compiles(one_chip, dims, c, block_dtype):
    k, d, n_pad, _ = dims
    _compile(one_chip, cs.community_spmm_ell,
             ((k, d, n_pad, n_pad), block_dtype), ((k, d), i32),
             ((k, d), f32), ((k, n_pad, c), f32), ((k,), i32),
             ((k, d), i32))


@pytest.mark.parametrize("dims,c", [(PAPER, 745), (PAPER, 1000), (PAPER, 8),
                                    (M32, 16), (M32, 32), (M32, 64)])
def test_packed_kernel_compiles(one_chip, dims, c):
    k, d, n_pad, rows = dims
    _compile(one_chip, cs.community_spmm_ell_packed,
             ((k, d, n_pad, n_pad), f32), ((k, d), i32), ((k, d), f32),
             ((rows, c), f32), ((k,), i32), ((k, d), i32))


@pytest.mark.parametrize("dims,c_in,c_out", [(PAPER, 745, 1000),
                                             (PAPER, 1000, 8),
                                             (M32, 16, 16), (M32, 64, 8)])
def test_fused_kernel_compiles(one_chip, dims, c_in, c_out):
    k, d, n_pad, rows = dims
    _compile(one_chip, cs.community_spmm_ell_fused,
             ((k, d, n_pad, n_pad), f32), ((k, d), i32), ((k, d), f32),
             ((rows, c_in), f32), ((c_in, c_out), f32), ((k,), i32),
             ((k, d), i32))


@pytest.mark.parametrize("total,tile,quantum", [
    (2552, 256, 8), (2552, 256, 128), (745, 256, 128), (1000, 256, 128),
    (48, 256, 8), (2560, 256, 128), (512, 256, 8), (8, 256, 128)])
def test_shrink_returns_aligned_or_full_tiles(total, tile, quantum):
    t = cs._shrink(total, tile, quantum)
    assert total % t == 0
    assert t == total or (t % quantum == 0 and t <= tile)
