"""Pallas kernel rules: block DMAs in bounds, VMEM within budget,
(8, 128)-aligned tiles.

These rules never run the kernel.  They abstract-interpret the
``KernelSpec`` the kernel itself is built from (``kernels/community_spmm``
exports ``spmm_spec``/``ell_spec``): each operand's index map is evaluated
at every grid *corner* (the maps are affine/monotone in the grid ids, so
extremes bound the interior) with the real scalar-prefetch arrays, and
data-dependent gathers (``ell_indices`` steering the Z DMA) are bounded by
the value range of the scalar array itself.

Context expectation: ``kernels`` is a list of dicts —

    {"spec": KernelSpec,                  # required
     "scalars": {name: np.ndarray, ...},  # the scalar-prefetch operands
     "vmem_budget": int}                  # optional, default: the kernels'
                                          # own scoped VMEM limit
"""
from __future__ import annotations

import itertools
from typing import Any, Iterable, List, Mapping, Optional

from repro.analysis.findings import Finding, Severity
from repro.analysis.registry import AnalysisContext, rule
from repro.kernels.community_spmm import LANE as _LANE
from repro.kernels.community_spmm import SUBLANE as _SUBLANE
from repro.kernels.community_spmm import VMEM_LIMIT_BYTES

# the scoped VMEM every community kernel requests from Mosaic
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES


def _grid_corners(grid: tuple) -> Iterable[tuple]:
    axes = [sorted({0, g - 1}) for g in grid]
    return itertools.product(*axes)


def check_kernel_bounds(spec: Any,
                        scalars: Optional[Mapping[str, Any]] = None
                        ) -> List[Finding]:
    """Every block index the grid can produce stays inside its operand.

    Importable directly (tests hand-build bad specs); the registry rule
    wraps it over ``expectations["kernels"]``.
    """
    scalars = scalars or {}
    findings: list[Finding] = []
    scalar_args = [scalars.get(n) for n in spec.scalar_prefetch]
    have_scalars = all(a is not None for a in scalar_args)
    for op in spec.operands:
        counts = op.block_counts()
        if op.index_map.__code__.co_argcount > len(spec.grid) \
                and not have_scalars:
            continue                     # cannot evaluate without scalars
        for corner in _grid_corners(spec.grid):
            try:
                idx = op.index_map(*corner, *scalar_args)
            except (IndexError, TypeError) as e:
                findings.append(Finding(
                    "pallas/index-bounds", Severity.ERROR,
                    f"{spec.name}:{op.name} index map failed at grid "
                    f"{corner}: {e}", location=f"{spec.name}:{op.name}",
                    details={"grid_point": list(corner)}))
                break
            bad = [(ax, int(v), int(c))
                   for ax, (v, c) in enumerate(zip(idx, counts))
                   if not 0 <= int(v) < c]
            if bad:
                ax, v, c = bad[0]
                findings.append(Finding(
                    "pallas/index-bounds", Severity.ERROR,
                    f"{spec.name}:{op.name} block index {v} out of range "
                    f"[0, {c}) on dim {ax} at grid point {corner}",
                    location=f"{spec.name}:{op.name}",
                    details={"grid_point": list(corner), "dim": ax,
                             "index": v, "blocks": c}))
                break
        if op.gather_scalar and op.gather_scalar in scalars:
            arr = scalars[op.gather_scalar]
            lo, hi = int(arr.min()), int(arr.max())
            limit = op.gather_limit()
            if lo < 0 or hi >= limit:
                findings.append(Finding(
                    "pallas/index-bounds", Severity.ERROR,
                    f"{spec.name}:{op.name} gathered via "
                    f"{op.gather_scalar} with values in [{lo}, {hi}] but "
                    f"only {limit} leading positions",
                    location=f"{spec.name}:{op.name}",
                    details={"scalar": op.gather_scalar, "min": lo,
                             "max": hi, "blocks": limit}))
    return findings


def check_kernel_vmem(spec: Any,
                      budget: int = VMEM_BUDGET_BYTES) -> List[Finding]:
    """Double-buffered block footprint + scratch fits the VMEM budget."""
    est = spec.vmem_bytes()
    if est > budget:
        return [Finding(
            "pallas/vmem-budget", Severity.ERROR,
            f"{spec.name}: estimated VMEM footprint {est} B exceeds "
            f"budget {budget} B",
            location=spec.name,
            details={"estimate": int(est), "budget": int(budget),
                     "per_operand": {op.name: op.block_bytes()
                                     for op in spec.operands},
                     "scratch": spec.scratch_bytes})]
    return []


def check_tile_alignment(spec: Any) -> List[Finding]:
    """Trailing block dims are (8, 128)-aligned (or span the full array
    dim) — Mosaic refuses to lower any other block for the TPU, so a
    finding here is a kernel that cannot run on the chip."""
    findings: list[Finding] = []
    for op in spec.operands:
        pairs = [(b, d) for b, d in zip(op.block_shape, op.array_shape)
                 if b is not None]
        if len(pairs) < 2:
            continue
        (sub_b, sub_d), (lane_b, lane_d) = pairs[-2], pairs[-1]
        bad = []
        if lane_b % _LANE and lane_b != lane_d:
            bad.append(f"lane dim {lane_b} not a multiple of {_LANE}")
        if sub_b % _SUBLANE and sub_b != sub_d:
            bad.append(f"sublane dim {sub_b} not a multiple of {_SUBLANE}")
        if bad:
            findings.append(Finding(
                "pallas/tile-alignment", Severity.ERROR,
                f"{spec.name}:{op.name} block "
                f"{tuple(b for b in op.block_shape)}: " + "; ".join(bad),
                location=f"{spec.name}:{op.name}",
                details={"block_shape": [b for b in op.block_shape]}))
    return findings


def _kernels(ctx: AnalysisContext) -> list[dict]:
    return list(ctx.expectations.get("kernels") or [])


@rule("pallas/index-bounds")
def index_bounds(ctx: AnalysisContext) -> Iterable[Finding]:
    """Abstract interpretation of each kernel's index maps (grid corners
    + scalar-prefetch value ranges) proves every block DMA in bounds."""
    for k in _kernels(ctx):
        yield from check_kernel_bounds(k["spec"], k.get("scalars"))


@rule("pallas/vmem-budget")
def vmem_budget(ctx: AnalysisContext) -> Iterable[Finding]:
    """Each kernel's estimated VMEM footprint fits its budget."""
    for k in _kernels(ctx):
        yield from check_kernel_vmem(
            k["spec"], k.get("vmem_budget", VMEM_BUDGET_BYTES))


@rule("pallas/tile-alignment")
def tile_alignment(ctx: AnalysisContext) -> Iterable[Finding]:
    """Block shapes land on (8, 128) VREG tile boundaries (or span the
    array dim) — the TPU compiler's own lowering condition."""
    for k in _kernels(ctx):
        yield from check_tile_alignment(k["spec"])
