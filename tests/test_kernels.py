"""Per-kernel validation: Pallas (interpret=True — executes the kernel body
on CPU) vs the pure-jnp oracle in ref.py, swept over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.community_spmm import (community_spmm, community_spmm_ell,
                                          community_spmm_ell_packed)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def _tol(dtype):
    # f32 tolerance covers matmul reassociation between tiled and dense paths
    return {"rtol": 2e-2, "atol": 2e-2} if dtype == jnp.bfloat16 \
        else {"rtol": 2e-4, "atol": 2e-4}


# ---------------------------------------------------------------------------
# community_spmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n_pad,c", [(3, 64, 32), (4, 128, 256),
                                       (2, 256, 48), (5, 72, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_community_spmm_matches_ref(m, n_pad, c, dtype):
    rng = np.random.default_rng(0)
    a = rng.normal(size=(m, n_pad, n_pad)).astype(np.float32)
    # block sparsity: zero some blocks and mask them
    mask = rng.random(m) > 0.3
    mask[0] = True
    a[~mask] = 0.0
    z = rng.normal(size=(m, n_pad, c)).astype(np.float32)
    a, z = jnp.asarray(a, dtype), jnp.asarray(z, dtype)
    maskj = jnp.asarray(mask)

    out = community_spmm(a, z, maskj, interpret=True)
    expect = ref.community_spmm_ref(a, z, maskj)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_community_spmm_skips_masked_blocks():
    """Masked blocks must not contribute even if their data is nonzero."""
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(3, 64, 64)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(3, 64, 16)).astype(np.float32))
    mask = jnp.asarray([True, False, True])
    out = community_spmm(a, z, mask, interpret=True)
    expect = ref.community_spmm_ref(a, z, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    # and differs from the unmasked product
    full = ref.community_spmm_ref(a, z, jnp.asarray([True] * 3))
    assert np.abs(np.asarray(out) - np.asarray(full)).max() > 1e-3


# ---------------------------------------------------------------------------
# community_spmm_ell (block-compressed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m_z,k,max_deg,n_pad,c", [
    (6, 6, 3, 64, 32),      # full layout (k == M)
    (8, 2, 4, 64, 48),      # shard slice (k < M, global indices)
    (4, 4, 1, 128, 128),    # single-neighbour rows
    (5, 5, 5, 72, 20),      # ragged: many padding lanes
])
def test_community_spmm_ell_matches_oracles(m_z, k, max_deg, n_pad, c):
    """Interpret-mode Pallas ELL kernel vs the einsum and loop oracles,
    with real max_deg padding lanes (mask 0, index 0) in the mix."""
    rng = np.random.default_rng(0)
    blocks = rng.normal(size=(k, max_deg, n_pad, n_pad)).astype(np.float32)
    idx = rng.integers(0, m_z, size=(k, max_deg)).astype(np.int32)
    # variable fan-in: row r keeps 1 + (r % max_deg) real slots
    mask = np.zeros((k, max_deg), np.float32)
    for r in range(k):
        mask[r, : 1 + r % max_deg] = 1.0
    z = rng.normal(size=(m_z, n_pad, c)).astype(np.float32)

    args = (jnp.asarray(blocks), jnp.asarray(idx), jnp.asarray(mask),
            jnp.asarray(z))
    out = community_spmm_ell(*args, interpret=True)
    expect = ref.community_spmm_ell_einsum(*args)
    loop = ref.community_spmm_ell_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(loop), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_community_spmm_ell_skips_padding_lanes():
    """Padding slots (mask 0) must not contribute even though they point at
    real z rows (index 0) and hold nonzero block data."""
    rng = np.random.default_rng(3)
    k, max_deg, n_pad, c = 3, 3, 64, 16
    blocks = jnp.asarray(rng.normal(size=(k, max_deg, n_pad, n_pad))
                         .astype(np.float32))
    idx = jnp.zeros((k, max_deg), jnp.int32)
    mask = jnp.asarray([[1, 0, 0], [1, 1, 0], [1, 1, 1]], jnp.float32)
    z = jnp.asarray(rng.normal(size=(4, n_pad, c)).astype(np.float32))

    out = community_spmm_ell(blocks, idx, mask, z, interpret=True)
    expect = ref.community_spmm_ell_einsum(blocks, idx, mask, z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    # and differs from the all-real-slot product
    full = ref.community_spmm_ell_einsum(blocks, idx,
                                         jnp.ones_like(mask), z)
    assert np.abs(np.asarray(out) - np.asarray(full)).max() > 1e-3


# ---------------------------------------------------------------------------
# community_spmm_ell_packed (packed receive plane, element-windowed Z)
# ---------------------------------------------------------------------------

def _plane_inputs(k, max_deg, n_pad, c, seed=0):
    """Slots packed back to back at 8-aligned offsets, bucket counts in
    multiples of 8, adjacency rows past a lane's count zero (the layout
    contract).  Adjacency columns past a neighbour's count are left
    NONZERO: a Z window that runs into the next slot's rows (or the
    plane's tail) must be zeroed by the kernel, not by the adjacency."""
    rng = np.random.default_rng(seed)
    n_slots = k + 2
    counts = 8 * rng.integers(1, n_pad // 8 + 1, size=n_slots)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    slot = rng.integers(0, n_slots, size=(k, max_deg))
    slot[0, 0] = n_slots - 1              # the window that ends the plane
    mask = np.zeros((k, max_deg), np.float32)
    for r in range(k):
        mask[r, : 1 + r % max_deg] = 1.0
    rows = (8 * rng.integers(1, n_pad // 8 + 1, size=k)).astype(np.int32)
    blocks = rng.normal(size=(k, max_deg, n_pad, n_pad)).astype(np.float32)
    blocks *= np.arange(n_pad)[None, None, :, None] < rows[:, None, None,
                                                           None]
    plane = rng.normal(size=(int(counts.sum()), c)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in
                 (blocks, offsets[slot], mask, plane, rows,
                  counts[slot].astype(np.int32)))


@pytest.mark.parametrize("k,max_deg,n_pad,c", [
    (2, 3, 32, 8),        # one contraction tile (full n_pad)
    (3, 2, 512, 16),      # two 256-row tiles, windows cross slots
    (2, 2, 72, 130),      # C not a lane multiple: full-width tile
])
def test_community_spmm_ell_packed_matches_oracle(k, max_deg, n_pad, c):
    args = _plane_inputs(k, max_deg, n_pad, c)
    out = community_spmm_ell_packed(*args, interpret=True)
    expect = ref.community_spmm_ell_packed_einsum(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,hd", [
    (2, 256, 4, 4, 64),     # MHA
    (1, 512, 8, 2, 64),     # GQA
    (2, 256, 4, 1, 128),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, s, hq, hkv, hd, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, s, hq, hd)).astype(np.float32), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)).astype(np.float32), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)).astype(np.float32), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(2)
    b, s, h, hd = 1, 512, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=128, block_k=128, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    rng = np.random.default_rng(3)
    b, s, h, hd = 2, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_attention():
    """The kernel agrees with the model's block_causal_attention path."""
    from repro.models.attention import block_causal_attention
    rng = np.random.default_rng(4)
    b, s, h, hd = 1, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    expect = block_causal_attention(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 32, 2, 32, 32),
    (1, 256, 2, 64, 1, 64, 64),
    (2, 64, 8, 16, 4, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(b, s, h, p, g, n, chunk, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32), dtype)
    dt = jnp.asarray(0.5 * np.abs(rng.normal(size=(b, s, h))).astype(np.float32))
    a = -jnp.asarray(np.abs(rng.normal(size=(h,))).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32), dtype)
    cm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32), dtype)
    y, _ = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    expect = ref.ssd_scan_ref(x.astype(jnp.float32), dt, a,
                              bm.astype(jnp.float32),
                              cm.astype(jnp.float32), chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_scan_chunk_invariance():
    """Different chunk sizes give the same result (state relay correct)."""
    rng = np.random.default_rng(5)
    b, s, h, p, g, n = 1, 128, 2, 16, 1, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(0.3 * np.abs(rng.normal(size=(b, s, h))).astype(np.float32))
    a = -jnp.asarray(np.abs(rng.normal(size=(h,))).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32))
    cm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32))
    y32, _ = ssd_scan(x, dt, a, bm, cm, chunk=32, interpret=True)
    y128, _ = ssd_scan(x, dt, a, bm, cm, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y128),
                               rtol=2e-4, atol=2e-4)
