"""Pallas TPU kernels: community-blocked sparse-dense matmul (Ã · Z).

The GCN ADMM hot spot is the aggregation ``Σ_r Ã_{m,r} Z_r``.  On TPU we do
NOT port a CSR gather-SpMM (no efficient per-element gather on the VPU);
instead the paper's community structure gives a *block*-sparse layout:
dense (n_pad × n_pad) community blocks with a (M × M) block mask — each
present block is a dense MXU matmul on 128-aligned VMEM tiles and absent
blocks are skipped with ``@pl.when`` (DESIGN.md §2, hardware adaptation).

Two kernels over the same math:

  * ``community_spmm`` — dense (M, n_pad, n_pad) block rows + neighbour
    mask; grid (row-tiles, col-tiles, M), the community (reduction) axis
    innermost so the output tile stays resident in VMEM.
  * ``community_spmm_ell`` — block-compressed (ELL) rows: only the max_deg
    stored neighbour blocks are iterated, and the gathered Z block for
    slot d is chosen *at DMA time* from the scalar-prefetched
    ``ell_indices`` (PrefetchScalarGridSpec), so the reduction is O(max_deg)
    instead of O(M) and absent/padding slots never touch the MXU.

  a_row:  (M, n_pad, n_pad)   this shard's row of Ã blocks
  z_all:  (M, n_pad, C)       gathered community features
  mask:   (M,)                neighbour mask (True = nonzero block)
  out:    (n_pad, C)

Both kernels derive their grid, block shapes and index maps from a
declarative ``KernelSpec`` (``spmm_spec`` / ``ell_spec``) which
``repro.analysis.rules.pallas`` abstract-interprets to bound every block
DMA against the operand shapes and to estimate the VMEM footprint — the
kernel and the linter read the *same* spec, so they cannot drift.

Tiling contract (what Mosaic accepts on v5e): every block's last two dims
are multiples of (8, 128) or span the whole array dim.  ``_shrink`` only
ever returns such tiles, so the contraction tile over ``n_pad`` is a
128-multiple divisor when one exists and the full ``n_pad`` otherwise,
and feature tiles are 128-multiples or the whole C.  The packed kernels
read neighbour rows at 8-aligned offsets of the receive plane through
element-indexed (``pl.Element``) Z windows, so a contraction tile may
start mid-plane without an 8-lane adjacency block.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_N = 256     # output rows per tile (a multiple of 8)
DEFAULT_TILE_C = 256     # feature cols per tile (a multiple of 128)
DEFAULT_TILE_P = 256     # contraction rows per tile (a multiple of 128)
SUBLANE, LANE = 8, 128   # the (8, 128) f32 VMEM tile of TPU v5e
# scoped VMEM each kernel may claim (v5e has 128 MiB per core; Mosaic's
# default scope is 16 MiB, below the full-width contraction blocks)
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


# ---------------------------------------------------------------------------
# Declarative kernel specs (shared by pallas_call and the static linter)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockOperand:
    """One pallas operand: array shape, block shape, and the index map.

    ``index_map`` has the exact pallas signature — grid ids first, then
    any scalar-prefetch operands — and works equally on traced refs (in
    the kernel) and numpy arrays (in the linter).  ``gather_scalar``
    names the scalar-prefetch array whose *values* select this operand's
    leading block (data-dependent DMA): the linter bounds that array's
    value range against the leading block count.

    ``element=True`` makes every block dim an element window
    (``pl.Element``): the index map returns element offsets, not block
    indices, and the gathered scalar counts ``gather_unit`` elements.
    ``tail_pad`` is the zero rows the kernel appends to the operand so a
    window starting on any real row stays in bounds; a gathered offset
    must address a real row (below ``array_shape[0] - tail_pad``).
    """
    name: str
    array_shape: tuple[int, ...]
    block_shape: tuple[Optional[int], ...]
    index_map: Callable[..., tuple]
    dtype_bytes: int = 4
    gather_scalar: Optional[str] = None
    element: bool = False
    gather_unit: int = 1
    tail_pad: int = 0

    def block_bytes(self) -> int:
        n = 1
        for b in self.block_shape:
            if b is not None:
                n *= b
        return n * self.dtype_bytes

    def block_counts(self) -> tuple[int, ...]:
        """Valid index range per dim (None dims index elements; element
        windows count their in-bounds start offsets)."""
        if self.element:
            return tuple(dim - b + 1 for dim, b in
                         zip(self.array_shape, self.block_shape))
        return tuple(dim if b is None else -(-dim // b)
                     for dim, b in zip(self.array_shape, self.block_shape))

    def gather_limit(self) -> int:
        """Exclusive bound on the gathered scalar's values."""
        if self.element:
            return -(-(self.array_shape[0] - self.tail_pad)
                     // self.gather_unit)
        return self.block_counts()[0]

    def pallas_block_spec(self):
        """The ``pl.BlockSpec`` this operand lowers to.  Element windows
        tell Mosaic their row offset is sublane-aligned (every gathered
        offset and contraction tile is a multiple of 8 rows)."""
        if not self.element:
            return pl.BlockSpec(self.block_shape, self.index_map)
        index_map = self.index_map

        def aligned(*args):
            *lead, row, col = index_map(*args)
            return (*lead, pl.multiple_of(row, SUBLANE),
                    pl.multiple_of(col, LANE))

        return pl.BlockSpec(tuple(pl.Element(b) for b in self.block_shape),
                            aligned)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Grid + operands (inputs then output) + scratch, linter-checkable."""
    name: str
    grid: tuple[int, ...]
    operands: tuple[BlockOperand, ...]
    scratch_bytes: int = 0
    scalar_prefetch: tuple[str, ...] = ()

    def vmem_bytes(self) -> int:
        """Footprint estimate: double-buffered operand/output blocks
        (pallas pipelines the DMAs) plus accumulator scratch."""
        return (2 * sum(op.block_bytes() for op in self.operands)
                + self.scratch_bytes)


def _shrink(total: int, tile: int, quantum: int) -> int:
    """Largest divisor of ``total`` that is at most ``tile`` and a
    multiple of ``quantum``; ``total`` itself when none is (a block equal
    to the array dim is always legal).  Never an unaligned partial tile."""
    if total <= tile:
        return total
    for t in range(tile - tile % quantum, 0, -quantum):
        if total % t == 0:
            return t
    return total


def spmm_spec(m: int, n_pad: int, c: int, *,
              tile_n: int = DEFAULT_TILE_N, tile_c: int = DEFAULT_TILE_C,
              a_bytes: int = 4, z_bytes: int = 4) -> KernelSpec:
    """Spec for the dense-block kernel (grid: row-tiles, col-tiles, M;
    the scalar-prefetched block mask gates each community step)."""
    tile_n = _shrink(n_pad, tile_n, SUBLANE)
    tile_c = _shrink(c, tile_c, LANE)
    return KernelSpec(
        name="community_spmm",
        grid=(n_pad // tile_n, c // tile_c, m),
        operands=(
            BlockOperand("a_row", (m, n_pad, n_pad),
                         (None, tile_n, n_pad),
                         lambda i, j, r, msk: (r, i, 0), a_bytes),
            BlockOperand("z_all", (m, n_pad, c),
                         (None, n_pad, tile_c),
                         lambda i, j, r, msk: (r, 0, j), z_bytes),
            BlockOperand("out", (n_pad, c), (tile_n, tile_c),
                         lambda i, j, r, msk: (i, j), z_bytes),
        ),
        scratch_bytes=tile_n * tile_c * 4,
        scalar_prefetch=("mask",))


def ell_spec(k: int, max_deg: int, n_pad: int, c: int, m_total: int, *,
             tile_n: int = DEFAULT_TILE_N, tile_c: int = DEFAULT_TILE_C,
             tile_p: Optional[int] = None,
             block_bytes: int = 4, z_bytes: int = 4) -> KernelSpec:
    """Spec for the ELL kernel (grid: k, row-tiles, col-tiles, max_deg,
    contraction-tiles; scalar-prefetched ``ell_indices`` steer the Z DMA)."""
    tile_n = _shrink(n_pad, tile_n, SUBLANE)
    tile_c = _shrink(c, tile_c, LANE)
    tile_p = _shrink(n_pad, tile_p or DEFAULT_TILE_P, LANE)
    return KernelSpec(
        name="community_spmm_ell",
        grid=(k, n_pad // tile_n, c // tile_c, max_deg, n_pad // tile_p),
        operands=(
            BlockOperand("ell_blocks", (k, max_deg, n_pad, n_pad),
                         (None, None, tile_n, tile_p),
                         lambda m, i, j, d, p, idx, msk, rows, nbr:
                         (m, d, i, p), block_bytes),
            BlockOperand("z_all", (m_total, n_pad, c),
                         (None, tile_p, tile_c),
                         lambda m, i, j, d, p, idx, msk, rows, nbr:
                         (idx[m, d], p, j), z_bytes,
                         gather_scalar="ell_indices"),
            BlockOperand("out", (k, n_pad, c), (None, tile_n, tile_c),
                         lambda m, i, j, d, p, idx, msk, rows, nbr:
                         (m, i, j), z_bytes),
        ),
        scratch_bytes=tile_n * tile_c * 4,
        scalar_prefetch=("ell_indices", "ell_mask",
                         "row_counts", "nbr_counts"))


def _plane_window(plane_rows: int, tile_p: int, c: int, tile_c: int,
                  z_bytes: int, index_map) -> BlockOperand:
    """The packed receive plane as an element-windowed Z operand: the
    kernel appends ``tile_p`` zero rows so a contraction tile that starts
    on a real row never reads past the plane, and the index map clamps
    dead tiles' starts (past a neighbour's rows, skipped by the
    ``nbr_counts`` guard) to the first padding row."""
    return BlockOperand("z_plane", (plane_rows + tile_p, c), (tile_p, tile_c),
                        index_map, z_bytes, gather_scalar="ell_offsets8",
                        element=True, gather_unit=SUBLANE, tail_pad=tile_p)


def ell_fused_spec(k: int, max_deg: int, n_pad: int, c_in: int, c_out: int,
                   plane_rows: int, *,
                   tile_n: int = DEFAULT_TILE_N,
                   block_bytes: int = 4, z_bytes: int = 4) -> KernelSpec:
    """Spec for the fused aggregation→GEMM kernel.

    Same packed-plane machinery as ``ell_packed_spec`` — the Z window
    reads the (plane_rows, C_in) receive plane at the scalar-prefetched
    8-row offsets — but the grid carries no feature-tile axis: the whole
    (tile_n, C_in) aggregated block accumulates in VMEM scratch across
    the (d, p) reduction steps, and at the last step the per-community
    Z-update GEMM against the VMEM-resident ``w`` block writes the
    (tile_n, C_out) output directly.  The aggregated stack exists only
    as that scratch tile — it never round-trips HBM
    (``repro.analysis.rules.pallas.check_kernel_vmem`` bounds the
    footprint against this spec).
    """
    tile_n = _shrink(n_pad, tile_n, SUBLANE)
    tile_p = _shrink(n_pad, DEFAULT_TILE_P, LANE)
    return KernelSpec(
        name="community_spmm_ell_fused",
        grid=(k, n_pad // tile_n, max_deg, n_pad // tile_p),
        operands=(
            BlockOperand("ell_blocks", (k, max_deg, n_pad, n_pad),
                         (None, None, tile_n, tile_p),
                         lambda m, i, d, p, off8, msk, rows, nbr:
                         (m, d, i, p), block_bytes),
            _plane_window(plane_rows, tile_p, c_in, c_in, z_bytes,
                          lambda m, i, d, p, off8, msk, rows, nbr:
                          (jnp.minimum(off8[m, d] * SUBLANE + p * tile_p,
                                       plane_rows), 0)),
            BlockOperand("w", (c_in, c_out), (c_in, c_out),
                         lambda m, i, d, p, off8, msk, rows, nbr:
                         (0, 0), z_bytes),
            BlockOperand("out", (k, n_pad, c_out), (None, tile_n, c_out),
                         lambda m, i, d, p, off8, msk, rows, nbr:
                         (m, i, 0), z_bytes),
        ),
        scratch_bytes=tile_n * c_in * 4,
        scalar_prefetch=("ell_offsets8", "ell_mask",
                         "row_counts", "nbr_counts"))


def ell_packed_spec(k: int, max_deg: int, n_pad: int, c: int,
                    plane_rows: int, *,
                    tile_n: int = DEFAULT_TILE_N, tile_c: int = DEFAULT_TILE_C,
                    block_bytes: int = 4, z_bytes: int = 4) -> KernelSpec:
    """Spec for the packed-plane ELL kernel.

    Z is the packed Σ-bucket-rows receive plane ``(plane_rows, C)`` —
    no ``(M, n_pad, C)`` stride.  The scalar-prefetched ``ell_offsets8``
    plane carries each stored neighbour's starting row *in 8-row units*
    (every bucket size and plane offset is a multiple of 8 rows), and the
    Z window for contraction step p starts at element row
    ``8 · off8[m, d] + p · tile_p``.  A window may run past the
    neighbour's rows into the next slot's; those rows are zeroed in the
    kernel (``nbr_counts``), so they add nothing to the contraction.
    """
    tile_n = _shrink(n_pad, tile_n, SUBLANE)
    tile_c = _shrink(c, tile_c, LANE)
    tile_p = _shrink(n_pad, DEFAULT_TILE_P, LANE)
    return KernelSpec(
        name="community_spmm_ell_packed",
        grid=(k, n_pad // tile_n, c // tile_c, max_deg, n_pad // tile_p),
        operands=(
            BlockOperand("ell_blocks", (k, max_deg, n_pad, n_pad),
                         (None, None, tile_n, tile_p),
                         lambda m, i, j, d, p, off8, msk, rows, nbr:
                         (m, d, i, p), block_bytes),
            _plane_window(plane_rows, tile_p, c, tile_c, z_bytes,
                          lambda m, i, j, d, p, off8, msk, rows, nbr:
                          (jnp.minimum(off8[m, d] * SUBLANE + p * tile_p,
                                       plane_rows), j * tile_c)),
            BlockOperand("out", (k, n_pad, c), (None, tile_n, tile_c),
                         lambda m, i, j, d, p, off8, msk, rows, nbr:
                         (m, i, j), z_bytes),
        ),
        scratch_bytes=tile_n * tile_c * 4,
        scalar_prefetch=("ell_offsets8", "ell_mask",
                         "row_counts", "nbr_counts"))


# ---------------------------------------------------------------------------
# Dense-block kernel
# ---------------------------------------------------------------------------


def _f32_dot(a, b):
    """The kernels' one MXU product: float32 operands at full float32
    precision (explicit — Mosaic's default contract precision may round
    the operands to bfloat16), accumulated in float32."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _spmm_kernel(mask_ref, a_ref, z_ref, o_ref, acc_scr):
    r = pl.program_id(2)
    n_r = pl.num_programs(2)

    @pl.when(r == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(mask_ref[r] != 0)
    def _accum():
        a = a_ref[...]                       # (tile_n, n_pad)
        z = z_ref[...]                       # (n_pad, tile_c)
        acc_scr[...] += _f32_dot(a, z)

    @pl.when(r == n_r - 1)
    def _write():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_c", "interpret"))
def community_spmm(a_row: jax.Array, z_all: jax.Array, mask: jax.Array,
                   *, tile_n: int = DEFAULT_TILE_N,
                   tile_c: int = DEFAULT_TILE_C,
                   interpret: bool = False) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    m, n_pad, _ = a_row.shape
    c = z_all.shape[-1]
    spec = spmm_spec(m, n_pad, c, tile_n=tile_n, tile_c=tile_c,
                     a_bytes=a_row.dtype.itemsize,
                     z_bytes=z_all.dtype.itemsize)
    a_op, z_op, out_op = spec.operands
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,     # block mask (SMEM)
        grid=spec.grid,
        in_specs=[a_op.pallas_block_spec(), z_op.pallas_block_spec()],
        out_specs=out_op.pallas_block_spec(),
        scratch_shapes=[_vmem_scratch(out_op.block_shape)],
    )
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_op.array_shape, z_all.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=spec.name,
    )(mask.astype(jnp.int32), a_row, z_all)


def _vmem_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# Block-compressed (ELL) variant: only the nnz blocks are materialized.
#
# The lane's neighbour blocks arrive pre-gathered in ELL form — row m holds
# its max_deg neighbour blocks plus padding — so the reduction axis is
# max_deg (~constant on power-law community graphs) instead of M.  The
# gathered feature block to multiply against is *data-dependent*
# (z_all[ell_indices[m, d]]): ``ell_indices`` is scalar-prefetched so the
# BlockSpec index_map can steer the Z DMA before the body runs, and padding
# slots (ell_mask == 0) skip the MXU work with ``@pl.when`` — the same
# predication trick as the dense kernel's absent-block skip.
#
# Ragged (size-aware) padding: two more scalar-prefetched planes,
# ``row_counts`` (k,) and ``nbr_counts`` (k, max_deg), carry each lane's
# true padded row count and each stored neighbour's.  The contraction axis
# is tiled (grid axis 4, ``tile_p``), and a tile is accumulated only when
# (a) the block is real, (b) the output row tile starts below the lane's
# row count and (c) the contraction tile starts below the neighbour's row
# count — pad rows drop out of the DMA+accumulate at tile granularity, so
# work tracks the bucketed community sizes instead of the global n_pad.
# With counts pinned at n_pad (the default) every guard is trivially live
# and the kernel is the historic global-pad program.
# ---------------------------------------------------------------------------


def _neighbour_rows(z, nbr_rows, p, tile_p: int):
    """Zero the Z tile's rows past the neighbour's own (as the oracle
    does).  Strided Z holds zeros there already; a packed window may run
    into the next slot's rows of the plane."""
    row = p * tile_p + jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
    return jnp.where(row < nbr_rows, z, 0.0)


def _spmm_ell_kernel(idx_ref, msk_ref, rows_ref, nbr_ref, a_ref, z_ref,
                     o_ref, acc_scr, *, tile_n: int, tile_p: int):
    """ELL accumulation over (d, p); shared by the strided kernel (Z
    blocks steered by ``ell_indices``) and the packed one (Z windows at
    ``ell_offsets8``) — only their index maps differ."""
    m = pl.program_id(0)
    i = pl.program_id(1)
    d = pl.program_id(3)
    p = pl.program_id(4)
    n_d = pl.num_programs(3)
    n_p = pl.num_programs(4)

    @pl.when((d == 0) & (p == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = ((msk_ref[m, d] != 0)
            & (i * tile_n < rows_ref[m])         # output rows are real
            & (p * tile_p < nbr_ref[m, d]))      # neighbour rows are real

    @pl.when(live)
    def _accum():
        a = a_ref[...].astype(jnp.float32)       # (tile_n, tile_p)
        z = _neighbour_rows(z_ref[...].astype(jnp.float32), nbr_ref[m, d],
                            p, tile_p)           # (tile_p, tile_c)
        acc_scr[...] += _f32_dot(a, z)

    @pl.when((d == n_d - 1) & (p == n_p - 1))
    def _write():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_c", "tile_p",
                                             "interpret"))
def community_spmm_ell(ell_blocks: jax.Array, ell_indices: jax.Array,
                       ell_mask: jax.Array, z_all: jax.Array,
                       row_counts: jax.Array | None = None,
                       nbr_counts: jax.Array | None = None,
                       *, tile_n: int = DEFAULT_TILE_N,
                       tile_c: int = DEFAULT_TILE_C,
                       tile_p: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Σ_d mask[m,d] · blocks[m,d] @ z_all[idx[m,d]] — O(nnz·n_pad²·C),
    and with ragged row counts O(Σ bucket_m · bucket_d · C) only.

    ell_blocks:  (k, max_deg, n_pad, n_pad) — a shard's ELL rows (f32 or
                 bf16; accumulation always f32)
    ell_indices: (k, max_deg) int32 global community ids into z_all
    ell_mask:    (k, max_deg) — nonzero = real block, 0 = padding slot
    z_all:       (M, n_pad, C) gathered community features
    row_counts:  optional (k,) int32 — lane m's padded rows; output row
                 tiles past it are skipped (written as zero)
    nbr_counts:  optional (k, max_deg) int32 — rows of each stored
                 neighbour; contraction tiles past it are skipped
    returns      (k, n_pad, C)
    """
    from jax.experimental.pallas import tpu as pltpu

    k, max_deg, n_pad, _ = ell_blocks.shape
    m_total, _, c = z_all.shape
    spec = ell_spec(k, max_deg, n_pad, c, m_total,
                    tile_n=tile_n, tile_c=tile_c, tile_p=tile_p,
                    block_bytes=ell_blocks.dtype.itemsize,
                    z_bytes=z_all.dtype.itemsize)
    a_op, z_op, out_op = spec.operands
    eff_tile_n = out_op.block_shape[1]
    eff_tile_p = z_op.block_shape[1]

    if row_counts is None:
        row_counts = jnp.full((k,), n_pad, jnp.int32)
    if nbr_counts is None:
        nbr_counts = jnp.full((k, max_deg), n_pad, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,     # ell_indices, ell_mask, rows, nbrs (SMEM)
        grid=spec.grid,
        in_specs=[a_op.pallas_block_spec(), z_op.pallas_block_spec()],
        out_specs=out_op.pallas_block_spec(),
        scratch_shapes=[_vmem_scratch(
            (out_op.block_shape[1], out_op.block_shape[2]))],
    )
    return pl.pallas_call(
        functools.partial(_spmm_ell_kernel, tile_n=eff_tile_n,
                          tile_p=eff_tile_p),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_op.array_shape, z_all.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=spec.name,
    )(ell_indices.astype(jnp.int32), ell_mask.astype(jnp.int32),
      row_counts.astype(jnp.int32), nbr_counts.astype(jnp.int32),
      ell_blocks, z_all)


def _packed_call(kernel, spec: KernelSpec, ell_blocks, ell_offsets,
                 ell_mask, z_plane, row_counts, nbr_counts, *extra,
                 interpret: bool):
    """pallas_call shared by the packed and fused kernels: offsets in
    8-row units (masked slots pinned at 0, so every prefetched value
    addresses the plane — the linter bounds the value range) and the
    plane padded with the window's ``tail_pad`` zero rows."""
    from jax.experimental.pallas import tpu as pltpu

    a_op, z_op, *_, out_op = spec.operands
    off8 = jnp.where(ell_mask != 0, ell_offsets // SUBLANE, 0)
    z_pad = jnp.pad(z_plane, ((0, z_op.tail_pad), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # offsets8, ell_mask, rows, nbrs (SMEM)
        grid=spec.grid,
        in_specs=[op.pallas_block_spec() for op in spec.operands[:-1]],
        out_specs=out_op.pallas_block_spec(),
        scratch_shapes=[_vmem_scratch(
            (out_op.block_shape[1], z_op.block_shape[1]))],
    )
    return pl.pallas_call(
        functools.partial(kernel, tile_n=out_op.block_shape[1],
                          tile_p=a_op.block_shape[3]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_op.array_shape, z_plane.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=spec.name,
    )(off8.astype(jnp.int32), ell_mask.astype(jnp.int32),
      row_counts.astype(jnp.int32), nbr_counts.astype(jnp.int32),
      ell_blocks, z_pad, *extra)


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_c", "interpret"))
def community_spmm_ell_packed(ell_blocks: jax.Array, ell_offsets: jax.Array,
                              ell_mask: jax.Array, z_plane: jax.Array,
                              row_counts: jax.Array,
                              nbr_counts: jax.Array,
                              *, tile_n: int = DEFAULT_TILE_N,
                              tile_c: int = DEFAULT_TILE_C,
                              interpret: bool = False) -> jax.Array:
    """ELL aggregation over the *packed* feature plane.

    Same math as ``community_spmm_ell`` but Z arrives as the packed
    Σ-bucket-rows receive plane instead of the (M, n_pad, C) stride —
    neighbour d of lane m occupies rows [offsets[m, d],
    offsets[m, d] + nbr_counts[m, d]).

    ell_blocks:  (k, max_deg, n_pad, n_pad) — f32 or bf16 ELL rows
    ell_offsets: (k, max_deg) int32 packed row offsets, 8-aligned;
                 masked-out slots may carry any in-plane value (0 is
                 conventional — their tiles are skipped)
    ell_mask:    (k, max_deg) — nonzero = real block
    z_plane:     (plane_rows, C), plane_rows a multiple of 8
    row_counts:  (k,) int32 — lane's true padded rows (8-aligned)
    nbr_counts:  (k, max_deg) int32 — each stored neighbour's rows
    returns      (k, n_pad, C) blocked output, rows past row_counts zero
    """
    k, max_deg, n_pad, _ = ell_blocks.shape
    plane_rows, c = z_plane.shape
    spec = ell_packed_spec(k, max_deg, n_pad, c, plane_rows,
                           tile_n=tile_n, tile_c=tile_c,
                           block_bytes=ell_blocks.dtype.itemsize,
                           z_bytes=z_plane.dtype.itemsize)
    return _packed_call(_spmm_ell_kernel, spec, ell_blocks,
                        ell_offsets, ell_mask, z_plane, row_counts,
                        nbr_counts, interpret=interpret)


# ---------------------------------------------------------------------------
# Fused aggregation→Z-update: one pass computes (Σ_d Ã[m,d] Z_d) @ W with
# the aggregated (tile_n, C_in) block held in VMEM scratch the whole time.
#
# The unfused pipeline runs the packed ELL aggregation and the Z-update
# GEMM as two XLA calls, writing the (k, n_pad, C_in) aggregate to HBM
# between them and reading it straight back.  Here the grid drops the
# feature-tile axis (GCN feature dims are narrow), the reduction over
# (d, p) accumulates into the same f32 scratch as the packed kernel — so
# the aggregate is *bitwise* the packed kernel's — and the final grid
# step applies the GEMM against the VMEM-resident W block and writes the
# (tile_n, C_out) result.  The aggregate never exists in HBM; the
# ``memory/fused-no-intermediate`` analysis rule proves the compiled
# trainer step keeps it that way.
# ---------------------------------------------------------------------------


def _spmm_ell_fused_kernel(off_ref, msk_ref, rows_ref, nbr_ref, a_ref,
                           z_ref, w_ref, o_ref, agg_scr, *,
                           tile_n: int, tile_p: int):
    m = pl.program_id(0)
    i = pl.program_id(1)
    d = pl.program_id(2)
    p = pl.program_id(3)
    n_d = pl.num_programs(2)
    n_p = pl.num_programs(3)

    @pl.when((d == 0) & (p == 0))
    def _init():
        agg_scr[...] = jnp.zeros_like(agg_scr)

    live = ((msk_ref[m, d] != 0)
            & (i * tile_n < rows_ref[m])         # output rows are real
            & (p * tile_p < nbr_ref[m, d]))      # neighbour rows are real

    @pl.when(live)
    def _accum():
        a = a_ref[...].astype(jnp.float32)       # (tile_n, tile_p)
        z = _neighbour_rows(z_ref[...].astype(jnp.float32), nbr_ref[m, d],
                            p, tile_p)           # (tile_p, c_in)
        agg_scr[...] += _f32_dot(a, z)

    @pl.when((d == n_d - 1) & (p == n_p - 1))
    def _write():
        w = w_ref[...].astype(jnp.float32)       # (c_in, c_out)
        o_ref[...] = _f32_dot(agg_scr[...], w).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def community_spmm_ell_fused(ell_blocks: jax.Array, ell_offsets: jax.Array,
                             ell_mask: jax.Array, z_plane: jax.Array,
                             w: jax.Array,
                             row_counts: jax.Array,
                             nbr_counts: jax.Array,
                             *, tile_n: int = DEFAULT_TILE_N,
                             interpret: bool = False) -> jax.Array:
    """(Σ_d mask[m,d] · blocks[m,d] @ plane[off[m,d]:...]) @ W in one pass.

    Operands are exactly ``community_spmm_ell_packed``'s plus the
    (C_in, C_out) Z-update weight block ``w``.  The aggregation
    accumulates in the same order (and the same f32 scratch) as the
    packed kernel — the intermediate aggregate is bitwise the unfused
    kernel's — and the closing GEMM is one f32 dot per output tile, so
    fused-vs-unfused *outputs* agree to dot-reassociation tolerance
    (~1e-6 at GCN widths), not bitwise: XLA is free to split the unfused
    ``agg @ w`` contraction differently.  Returns (k, n_pad, C_out) with
    rows past ``row_counts`` zero.
    """
    k, max_deg, n_pad, _ = ell_blocks.shape
    plane_rows, c_in = z_plane.shape
    c_out = w.shape[-1]
    spec = ell_fused_spec(k, max_deg, n_pad, c_in, c_out, plane_rows,
                          tile_n=tile_n,
                          block_bytes=ell_blocks.dtype.itemsize,
                          z_bytes=z_plane.dtype.itemsize)
    return _packed_call(_spmm_ell_fused_kernel, spec, ell_blocks,
                        ell_offsets, ell_mask, z_plane, row_counts,
                        nbr_counts, w, interpret=interpret)
