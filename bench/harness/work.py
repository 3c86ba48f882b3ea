"""The work a round requires, counted from the graph and the layer widths
alone: N, nnz(Ã) = 2E + N and C_0 … C_L.  Never from the padded block
layout, the kernel tiling or the trace, so a sparser or fused program is
measured against the same yardstick.  And the chip's peaks.
"""
from __future__ import annotations

F32 = 4
INDEX = 4

# Published peaks per chip, keyed by jax's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def model_flops_per_round(n: int, nnz: int, dims) -> float:
    """One full-batch GCN training epoch, 3 × the forward pass.  Layer l's
    forward: the aggregation at the narrower side, 2·nnz·min(C_in, C_out),
    and the GEMM, 2·N·C_in·C_out."""
    fwd = sum(2.0 * nnz * min(c_in, c_out) + 2.0 * n * c_in * c_out
              for c_in, c_out in zip(dims[:-1], dims[1:]))
    return 3.0 * fwd


def aggregations(dims) -> list[tuple[str, int]]:
    """The sparse aggregations Ã·X that one round of the paper's updates
    requires, each counted once, with the width it runs at (after
    reassociating Ã(ZW) where that is narrower)."""
    n_l = len(dims) - 1
    out = []
    for l in range(1, n_l + 1):
        # eq. (2), W_l: Ã Z_{l-1}^k; reused by the Z_l target f(Ã Z W⁺),
        # the relay Ã Z_{l-1} W_l of eq. (5)/(6) and B of eq. (7)
        out.append((f"eq2 W{l}: A Z{l - 1}", dims[l - 1]))
    for l in range(1, n_l):
        # eq. (5)/(6), Z_l: the gradient of the coupling term, Ãᵀ R W_{l+1}ᵀ,
        # and its value at the accepted step, Ã (Z_l⁺ − Z_l^k) W_{l+1}
        out.append((f"eq5/6 Z{l}: grad A^T R W{l + 1}^T", dims[l + 1]))
        out.append((f"eq5/6 Z{l}: value A dZ{l} W{l + 1}", dims[l + 1]))
    # eq. (3), U: Ã Z_{L-1}^{k+1} W_L^{k+1}, on the new iterates
    out.append((f"eq3 U: A Z{n_l - 1}+ W{n_l}+", dims[n_l]))
    return out


def aggregation_cost(n: int, nnz: int, width: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one Ã·X at ``width`` columns: 2·nnz·width
    operations; Ã's nonzeros with their column indices, X read and the
    result written."""
    flops = 2.0 * nnz * width
    bytes_ = nnz * (F32 + INDEX) + 2.0 * n * width * F32
    return flops, bytes_


def aggregation_least_s(n: int, nnz: int, dims, peak: dict) -> float:
    """Least time of a round's aggregations: each bounded by the larger of
    its FLOPs over peak FLOP/s and its bytes over peak bytes/s."""
    total = 0.0
    for _, width in aggregations(dims):
        flops, bytes_ = aggregation_cost(n, nnz, width)
        total += max(flops / peak["flops"], bytes_ / peak["bytes"])
    return total
