"""Parallel (community-distributed) ADMM trainer — Algorithm 1 on a mesh.

Each shard on the ``comm`` mesh axis hosts ``k = M / n_shards`` community
agents (the paper's agents; k=1 when every community gets its own device).
One ADMM iteration is a single ``shard_map``-ed program:

  * W update — layer-parallel (Jacobi): per-shard φ contributions and grads
    are ``psum``-ed; the backtracking condition is evaluated on the global
    objective, so every shard takes the identical accepted τ step (this
    replaces the paper's dedicated agent M+1 with a replicated computation —
    TPU-native, no parameter server).
  * Z update — community-parallel: each community solves its ψ_{l,m}
    (eq. 5/6) locally from gathered relay aggregates (messages.py) with its
    own backtracking θ_{l,m} (lane-masked, so communities sharing a device
    still line-search independently); Z_L via per-community FISTA (eq. 7).
  * U update — local dual ascent (eq. 3).

Communication per iteration (the roofline 'collective' term) is one
exchange of Z/U/q per consumer round; the paper's p/s messages are exactly
the relayed aggregates, see messages.py.  Z_0 is static input — the
trainer aggregates Ã·Z_0 once at construction and every consumer reads it
(layer-1 input, the 1-layer dual refresh, the eval programs), so it is not
exchanged at all, but on the fused packed wire, whose one-pass Ã(Z_0 W)
Z sites gather it once per iteration.  Two transports (``transport`` flag):

  * allgather — ``lax.all_gather`` moves every shard's payload to every
    shard, then masks to the neighbour rows.  The only transport the dense
    adjacency supports (its Z-coupling reads all M rows), and the parity
    oracle for p2p.
  * p2p (default for ``compressed=True``) — neighbour-only exchange over a
    static round schedule (messages.NeighborExchange): the community
    topology is lifted to shard-to-shard edges (per-shard union of the ELL
    neighbour indices, graph.shard_neighbor_graph), messages are coloured
    into ``lax.ppermute`` rounds by ring offset (sharding.partition.
    ring_round_coloring — each round is a partial permutation, inactive
    offsets are skipped), and each round moves a padded
    ``(rows_pad, n_pad, C)`` send buffer.  Every shard receives only the
    lane-major ``(r_pad, n_pad, C)`` buffer of rows its subproblems
    actually read — no ``(M, n_pad, C)`` gathered tensor is materialised —
    and the ELL indices are remapped host-side to receive-buffer slots.
    ``comm_stats`` records the scheduled ``wire_bytes`` ==
    true rows + round padding ≤ the all-gather ``full_bytes``, with the
    true rows bounded by the mask-derived ``needed_bytes`` (verified at
    construction by messages.verify_transport_bytes; with one community
    per shard the bound holds padding-included and the CI benchmark
    guards assert it strictly).

Adjacency representations (``compressed`` flag):

  * dense — every shard holds its k rows of the (M, M, n_pad, n_pad) block
    tensor: O(k·M·n_pad²) bytes per shard, and the Z-update coupling term
    sums over all M communities (masked): O(M·n_pad²·C) FLOPs per lane.
  * compressed — each shard holds only its lanes' ELL rows,
    (k, max_deg, n_pad, n_pad) blocks + (k, max_deg) indices/mask
    (graph.BlockCSR): O(k·max_deg·n_pad²) bytes per shard, no dense block
    tensor anywhere on device.  Aggregations run through the lane-aware ELL
    kernel (kernels.community_spmm_ell) and the coupling term is its
    transposed-block form over the max_deg neighbours only:
    O(max_deg·n_pad²·C) FLOPs per lane.  On power-law community graphs
    max_deg is ~constant in M, so per-shard memory and Z-coupling FLOPs
    stop scaling with the community count — the regime where M can grow
    past what a dense replicated layout fits on device.

Padding (``pad_mode`` flag, default "bucketed"): packed tensors keep the
fixed (M, n_pad, ...) stride, but under the bucketed scheme every
community is *logically* padded only to its power-of-two-ish size bucket
(graph.bucket_pad_sizes) — the ELL kernel's scalar-prefetched row counts
guard the pad rows out of the DMA+accumulate, the p2p transport wires
row-exact payloads (a wired community contributes its true rows, not an
n_pad block), and ``comm_stats`` reports the residual padding as
``pad_rows``/``pad_bytes``/``pad_flops``/``pad_flop_frac``
(messages.pad_stats).  "global" restores the historic
everything-pads-to-the-max behaviour; the iterates are identical either
way (pad rows are zero throughout), only processed/wired volume changes.
``adjacency_bf16=True`` (compressed only) additionally stores the ELL
block plane bf16 — half the resident adjacency bytes, f32 accumulation.

Packed device state (``packed`` flag, requires compressed + p2p): the
resident trainer state drops the (M, n_pad, …) stride entirely.  Z/U and
the static z0/labels/masks live as Σ-bucket-rows planes — each shard
holds a ``(plane_rows, C)`` plane of its lanes' bucket rows back to back
(graph.PackedDeviceLayout), so resident state bytes track the bucketed
community sizes, not M × the largest community.  The exchange runs on
the packed plane (messages.exchange_neighbors_packed — same ppermute
rounds, byte-identical wire) into a packed receive plane, and the ELL
aggregation reads it through scalar-prefetched row offsets
(kernels community_spmm_ell_packed / NeighborExchange.localized_offsets)
instead of an n_pad stride.  Subproblem math runs on blocked per-lane
views rebuilt with static take-with-fill tables — pad rows are zero
throughout (the zero-outside-counts contract), so packed iterates are
*bitwise* equal to the strided path's.  ``overlap=True`` (packed only)
additionally splits each exchange into its round-indexed buffer stages
and aggregates each arrival group as soon as its rounds are in
(double-buffering wire behind compute; the sum association changes, so
overlap parity is tolerance- rather than bit-level), and ``comm_stats``
gains an analytic overlap-efficiency metric (messages.overlap_stats)
the roofline prices exposed wire with.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from functools import partial
from typing import TYPE_CHECKING, NamedTuple

if TYPE_CHECKING:
    from repro.core.serial import TrainLog

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import gcn, graph, messages
from repro.core.subproblems import ADMMConfig, stale_weights
from repro.sharding.partition import CommunityBatchSampler
from repro.util import spans

Array = jax.Array
AXIS = "comm"


class BoundProgram:
    """A jitted trainer program with the device data bound as its leading
    argument.  Closed-over arrays would be baked into the compiled program
    as constants — at the paper's widths a quarter-gigabyte ELL literal
    per program — so the data travels as a real, already-placed argument
    while callers keep the ``program(state, ...)`` signature (``lower``
    for the analysis passes)."""

    def __init__(self, fn, data):
        self.fn, self.data = fn, data

    def __call__(self, *args):
        return self.fn(self.data, *args)

    def lower(self, *args):
        return self.fn.lower(self.data, *args)


def place_on_mesh(mesh: Mesh, tree, spec):
    """``device_put`` every array leaf of ``tree`` with ``spec`` on the
    mesh (a PartitionSpec, or a matching tree of them)."""
    return jax.device_put(tree, jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda s: isinstance(s, P)))


class ParallelState(NamedTuple):
    """Trainer iterates.  Strided layout: zs[l] is (M, n_pad, C_l) and u
    (M, n_pad, C_L), sharded over comm.  Packed layout: zs[l] is the
    (n_shards · plane_rows, C_l) Σ-bucket-rows plane (u likewise) —
    shard s's slice holds its lanes' bucket rows back to back."""
    weights: tuple[Array, ...]   # replicated
    zs: tuple[Array, ...]        # sharded over comm
    u: Array                     # sharded
    taus: tuple[Array, ...]      # scalars, replicated
    thetas: tuple[Array, ...]    # (M,), sharded
    # (n_shards, len(probe_columns(L))) int32, sharded: per shard, since
    # construction, the line-search objective evaluations and the searches
    # that ran all ``max_backtracks`` iterations (``probe_count``), summed
    # over every search and then for each search on its own
    probes: Array


@dataclasses.dataclass(frozen=True)
class CommunityData:
    """Device-ready community-blocked graph tensors.

    Exactly one adjacency representation is resident: dense mode holds
    ``a_blocks`` (M, M, n_pad, n_pad); compressed mode holds only the ELL
    view ``ell_blocks``/``ell_indices``/``ell_mask`` (graph.BlockCSR,
    O(nnz·n_pad²) bytes) and ``a_blocks`` is None — the shard_map trainer
    aggregates straight from the sharded ELL rows.  With
    ``adjacency_bf16=True`` (compressed only) the ELL block store is kept
    bf16 on device — half the resident adjacency bytes — and every
    aggregation accumulates in f32 (the kernel's scratch / the oracle's
    explicit upcast).

    ``row_counts``/``nbr_counts`` carry the ragged (bucketed) per-lane and
    per-neighbour padded row counts the ELL kernel's pad-row guards key
    off; ``row_mask`` masks packed (M, n_pad) tensors down to true rows
    (metrics / Lagrangian).  Under the global pad scheme the counts are
    simply n_pad everywhere.

    With ``packed_layout`` set (graph.PackedDeviceLayout), z0 / labels /
    train_mask / test_mask are stored as Σ-bucket-rows planes —
    (n_shards · plane_rows, …) instead of (M, n_pad, …) — matching the
    packed trainer state; ``row_mask`` stays blocked (it only feeds the
    host-jit metrics, which unpack the planes anyway).
    """
    a_blocks: "Array | None"   # (M, M, n_pad, n_pad) — dense mode only
    z0: Array            # (M, n_pad, C0) | packed (total_rows, C0)
    labels: Array        # (M, n_pad) int32 | packed (total_rows,)
    train_mask: Array    # (M, n_pad) f32 | packed (total_rows,)
    test_mask: Array     # (M, n_pad) f32 | packed (total_rows,)
    neighbor_mask: Array  # (M, M) bool
    denom: Array         # scalar — global labeled-node count
    row_mask: Array       # (M, n_pad) float32 — 1 = true node row
    # block-compressed Ã (ELL view) — compressed mode only
    ell_blocks: "Array | None" = None    # (M, max_deg, n_pad, n_pad)
    ell_indices: "Array | None" = None   # (M, max_deg) int32
    ell_mask: "Array | None" = None      # (M, max_deg) float32
    row_counts: "Array | None" = None    # (M,) int32
    nbr_counts: "Array | None" = None    # (M, max_deg) int32
    packed_layout: "graph.PackedDeviceLayout | None" = None

    @property
    def compressed(self) -> bool:
        return self.a_blocks is None

    @property
    def packed(self) -> bool:
        return self.packed_layout is not None

    @property
    def adjacency_bf16(self) -> bool:
        return (self.ell_blocks is not None
                and self.ell_blocks.dtype == jnp.bfloat16)

    @property
    def num_parts(self) -> int:
        if self.packed_layout is not None:
            return self.packed_layout.num_parts
        return int(self.z0.shape[0])

    @property
    def adjacency_nbytes(self) -> int:
        """Device-resident adjacency bytes of this representation."""
        if self.compressed:
            return (self.ell_blocks.nbytes + self.ell_indices.nbytes
                    + self.ell_mask.nbytes)
        return self.a_blocks.nbytes


def community_data(g: graph.Graph, layout: graph.CommunityLayout,
                   compressed: bool = False,
                   adjacency_bf16: bool = False,
                   device_layout: "graph.PackedDeviceLayout | None" = None
                   ) -> CommunityData:
    if adjacency_bf16 and not compressed:
        raise ValueError("adjacency_bf16=True requires compressed=True — "
                         "only the ELL block store has a bf16 path")
    if device_layout is not None and not compressed:
        raise ValueError("packed device state requires compressed=True — "
                         "the dense block tensor keeps the n_pad stride")
    if compressed:
        csr = layout.compress()
        rows, nbrs = csr.ell_row_counts()
        block_dt = jnp.bfloat16 if adjacency_bf16 else jnp.float32
        adj = {"a_blocks": None,
               "ell_blocks": jnp.asarray(csr.ell_blocks, dtype=block_dt),
               "ell_indices": jnp.asarray(csr.ell_indices),
               "ell_mask": jnp.asarray(csr.ell_mask),
               "row_counts": jnp.asarray(rows),
               "nbr_counts": jnp.asarray(nbrs)}
    else:
        adj = {"a_blocks": jnp.asarray(layout.a_blocks)}
    if device_layout is not None:
        # Σ-bucket-rows planes: pad rows outside the bucket counts are
        # zero by the layout contract, so pack is lossless
        def dev(x):
            return np.asarray(device_layout.pack_state(layout.pack(x)))
    else:
        def dev(x):
            return layout.pack(x)
    return CommunityData(
        z0=jnp.asarray(dev(g.features)),
        labels=jnp.asarray(dev(g.labels.astype(np.int32))),
        train_mask=jnp.asarray(dev(g.train_mask.astype(np.float32))),
        test_mask=jnp.asarray(dev(g.test_mask.astype(np.float32))),
        neighbor_mask=jnp.asarray(layout.neighbor_mask),
        denom=jnp.asarray(float(g.train_mask.sum())),
        row_mask=jnp.asarray(layout.node_mask.astype(np.float32)),
        packed_layout=device_layout,
        **adj,
    )


# ---------------------------------------------------------------------------
# aggregation order: Ã·Z·W at the narrow side
# ---------------------------------------------------------------------------

def gathered_widths(layer_dims, fused: bool = False) -> list[int]:
    """Payload width of each gather in one ADMM iteration: Z_1..Z_L, then
    for L >= 2 the relay q per hidden layer, U, and the dual's refresh
    Z_{L-1}⁺.  A 1-layer net has no hidden Z loop (no q, no U gather).
    Z_0 crosses the wire only on the ``fused`` packed wire, once, for the
    one-pass Ã(Z_0 W) of its Z sites; every other mode reads the
    construction-time Ã·Z_0 (``ParallelADMMTrainer``'s ``ax``)."""
    dims = list(layer_dims)
    cs = ([dims[0]] if fused else []) + dims[1:]
    if len(dims) > 2:
        cs += dims[2:] + [dims[-1], dims[-2]]
    return cs


def narrow_first_agg(agg, widths: list, seeds=()):
    """``agg_mm(z, w)`` = Ã·z·w through ``agg(z)`` = Ã·z.  A ``z`` whose
    aggregate is at hand — ``seeds`` holds (z, Ã·z) pairs, as the eval
    programs seed (Z_0, ``ax``) — is (Ã z) w, a GEMM with no aggregation;
    any other is aggregated at the narrower side: Ã(z w) when ``w``
    narrows (C_out < C_in), else (Ã z) w.  A repeated (z, w) pair reuses
    its first result.  ``widths`` is cleared, then gets the width of each
    aggregation as it is traced."""
    widths.clear()
    done = []

    def agg_mm(z, w):
        for z_seen, w_seen, out in done:
            if z_seen is z and w_seen is w:
                return out
        az = next((a for z_seed, a in seeds if z_seed is z), None)
        if az is not None:
            out = az @ w
        else:
            narrow = w.shape[1] < w.shape[0]
            x = z @ w if narrow else z
            widths.append(int(x.shape[-1]))
            out = agg(x) if narrow else agg(x) @ w
        done.append((z, w, out))
        return out
    return agg_mm


# ---------------------------------------------------------------------------
# trainer configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Every mode flag of ``ParallelADMMTrainer``, validated in one place.

    The flags form a dependency ladder the trainer's subsystems rely on —
    packed planes only route through ELL offsets, the row-exact exchange
    only feeds packed planes, sampling only restricts a p2p round
    schedule — and ``__post_init__`` enforces the whole ladder with the
    same messages the trainer's historic inline checks raised, so every
    construction path (presets, CLI, benchmarks, the deprecation shim)
    fails identically.  ``transport=None`` resolves here exactly as the
    trainer historically did: p2p when compressed, the all-gather oracle
    otherwise.  ``partitioner=None`` stays None — its resolution depends
    on whether a precomputed partition is supplied, which only the
    trainer knows.

    Minibatching (``batch_fraction`` not None) engages stochastic
    community sampling: each ADMM round runs the W/Z/U sweep on a seeded
    shard batch only (sharding.partition.CommunityBatchSampler), with
    unsampled communities' consensus terms carried at their stale
    iterates under a ``stale_decay``-damped penalty
    (subproblems.stale_weights).  ``batch_fraction=1.0`` samples every
    shard every round and is bitwise-identical to the full-batch packed
    trainer; ``None`` (the default) builds no sampling machinery at all.
    Sampling composes with ``overlap=True``: each compiled batch derives
    its arrival-group schedule from its own restricted sub-plan.

    ``fused=True`` (requires ``packed``) routes the Z-update sites —
    target/relay/dual aggregation followed by a GEMM — through the fused
    aggregation→GEMM path (kernels.ops.community_spmm_ell_fused): the
    aggregated (k, n_pad, C) stack stays in VMEM scratch (TPU) or is
    reassociated away (oracle), never materialised in HBM.  The W-update
    keeps the raw aggregate (its line search re-evaluates the GEMM under
    a varying W — fusing there would repeat the whole aggregation per
    backtracking probe).  Inert on 1-shard meshes (no packed wire), where
    the program is bitwise the unfused one; multi-shard fused-vs-unfused
    parity is dot-reassociation tolerance.
    """
    compressed: bool = False
    transport: "str | None" = None
    partitioner: "str | None" = None
    pad_mode: str = "bucketed"
    packed: bool = False
    overlap: bool = False
    fused: bool = False
    comm_bf16: bool = False
    adjacency_bf16: bool = False
    use_kernel: bool = False
    batch_fraction: "float | None" = None
    stale_decay: float = 0.5
    sample_seed: int = 0

    def __post_init__(self):
        transport = self.transport
        if transport is None:
            transport = "p2p" if self.compressed else "allgather"
            object.__setattr__(self, "transport", transport)
        if transport not in ("p2p", "allgather"):
            raise ValueError(f"unknown transport {transport!r}; "
                             f"expected 'p2p' or 'allgather'")
        if transport == "p2p" and not self.compressed:
            raise ValueError("transport='p2p' requires compressed=True — "
                             "the dense Z-coupling reads all M payload rows")
        if self.packed and not self.compressed:
            raise ValueError("packed=True requires compressed=True — the "
                             "packed plane is only routed through ELL "
                             "offsets, never a dense Z-coupling")
        if self.packed and transport != "p2p":
            raise ValueError("packed=True requires transport='p2p' — the "
                             "plane layout exists to feed the row-exact "
                             "exchange; an all-gather would re-materialise "
                             "the strided (M, n_pad, C) payload")
        if self.overlap and not self.packed:
            raise ValueError("overlap=True requires packed=True — the "
                             "staged exchange snapshots are packed planes")
        if self.fused and not self.packed:
            raise ValueError("fused=True requires packed=True — the fused "
                             "aggregation→GEMM kernel reads the packed "
                             "receive plane through ELL offsets")
        if self.pad_mode not in ("global", "bucketed"):
            raise ValueError(f"unknown pad_mode {self.pad_mode!r}; "
                             f"expected 'global' or 'bucketed'")
        if self.adjacency_bf16 and not self.compressed:
            raise ValueError("adjacency_bf16=True requires compressed=True")
        if self.batch_fraction is not None:
            if not 0.0 < self.batch_fraction <= 1.0:
                raise ValueError(f"batch_fraction must be in (0, 1], got "
                                 f"{self.batch_fraction!r}")
            if not self.packed:
                raise ValueError("batch_fraction requires packed=True — "
                                 "the sampled sweep runs on the sampled "
                                 "shards' packed planes")
        if not 0.0 < self.stale_decay <= 1.0:
            raise ValueError(f"stale_decay must be in (0, 1], got "
                             f"{self.stale_decay!r}")

    @classmethod
    def from_cli_args(cls, args) -> "TrainerConfig":
        """Build from an argparse namespace (examples' CLI): every flag
        is read by its ``dest`` name, missing attributes keep the field
        default — one mapping instead of a kwarg list per driver."""
        kw = {}
        for f in dataclasses.fields(cls):
            if hasattr(args, f.name):
                kw[f.name] = getattr(args, f.name)
        return cls(**kw)


# named presets — attached after the class body because ``packed`` is
# both a field and a constructor name (a def inside the class body would
# shadow the dataclass field's default)
def _preset_dense(cls, **kw) -> TrainerConfig:
    """The dense-adjacency all-gather baseline."""
    kw.setdefault("compressed", False)
    return cls(**kw)


def _preset_p2p(cls, **kw) -> TrainerConfig:
    """Block-compressed adjacency over the neighbour-only p2p transport."""
    kw.setdefault("compressed", True)
    kw.setdefault("transport", "p2p")
    return cls(**kw)


def _preset_packed(cls, **kw) -> TrainerConfig:
    """Packed Σ-bucket-rows resident state over row-exact p2p."""
    kw.setdefault("compressed", True)
    kw.setdefault("transport", "p2p")
    kw.setdefault("packed", True)
    return cls(**kw)


def _preset_minibatch(cls, batch_fraction: float = 0.25,
                      **kw) -> TrainerConfig:
    """Stochastic community minibatching on the packed trainer."""
    kw.setdefault("compressed", True)
    kw.setdefault("transport", "p2p")
    kw.setdefault("packed", True)
    kw.setdefault("batch_fraction", batch_fraction)
    return cls(**kw)


TrainerConfig.dense = classmethod(_preset_dense)
TrainerConfig.p2p = classmethod(_preset_p2p)
TrainerConfig.packed = classmethod(_preset_packed)
TrainerConfig.minibatch = classmethod(_preset_minibatch)

# the historic flag kwargs the deprecation shim still accepts
_LEGACY_FLAGS = ("use_kernel", "comm_bf16", "compressed", "transport",
                 "partitioner", "pad_mode", "adjacency_bf16", "packed",
                 "overlap")


# ---------------------------------------------------------------------------
# backtracking primitives
# ---------------------------------------------------------------------------

def probe_columns(num_layers: int) -> list[str]:
    """Names of the columns of ``ParallelState.probes``: a round's
    objective evaluations and capped searches summed over all its line
    searches, then the same pair for each search in the order the round
    runs them, W_1 … W_L, the hidden Z_1 … Z_{L-1}, and Z_L's FISTA (its
    ``fista_iters`` searches summed)."""
    searches = [f"w{l}" for l in range(1, num_layers + 1)] + \
        [f"z{l}" for l in range(1, num_layers + 1)]
    return ["evals", "capped"] + [f"{s}.{c}" for s in searches
                                  for c in ("evals", "capped")]


def probe_count(iters, admm: ADMMConfig):
    """``[evaluations, capped]`` (int32) of one line search whose ``while``
    ran ``iters`` iterations: the first test plus one objective evaluation
    per iteration, and 1 when it ran all ``max_backtracks`` of them."""
    iters = jnp.asarray(iters, jnp.int32)
    return jnp.stack([iters + 1,
                      (iters >= admm.max_backtracks).astype(jnp.int32)])


def backtracking_step_psum(local_obj, x, tau0, admm: ADMMConfig):
    """Majorize-minimize step on the *global* objective psum(local_obj):
    every shard evaluates the same condition and accepts the same τ.
    Returns the step, τ and the search's ``probe_count``."""
    val_loc, grad_loc = jax.value_and_grad(local_obj)(x)
    val = jax.lax.psum(val_loc, AXIS)
    grad = jax.lax.psum(grad_loc, AXIS)
    g_sq = jnp.vdot(grad, grad).real

    def global_obj(w):
        return jax.lax.psum(local_obj(w), AXIS)

    def cond(carry):
        tau, it = carry
        x_new = x - grad / tau
        bound = val - 0.5 * g_sq / tau
        tol = admm.backtrack_rtol * (jnp.abs(bound) + 1e-12)
        return (bound + tol < global_obj(x_new)) & \
            (it < admm.max_backtracks)

    def body(carry):
        tau, it = carry
        return tau * admm.backtrack_growth, it + 1

    tau0 = jnp.maximum(tau0 / admm.backtrack_growth, 1e-8)
    tau, iters = jax.lax.while_loop(cond, body, (tau0, jnp.asarray(0)))
    return x - grad / tau, tau, probe_count(iters, admm)


def backtracking_step_lanes(obj_lanes, x, theta0, admm: ADMMConfig):
    """Per-lane majorize-minimize step (paper's per-(l,m) θ backtracking).

    obj_lanes: (k, n, C) -> (k,) per-community objective values.
    x: (k, n, C); theta0: (k,).  Lanes line-search independently: the loop
    runs until every lane accepts, frozen lanes stop doubling.  Returns the
    step, θ and the search's ``probe_count`` (one evaluation covers every
    lane of the shard).
    """
    vals = obj_lanes(x)                                  # (k,)
    grads = jax.grad(lambda z: obj_lanes(z).sum())(x)    # (k, n, C) separable
    g_sq = jnp.sum(grads * grads, axis=(1, 2))           # (k,)

    def accepted(theta):
        x_new = x - grads / theta[:, None, None]
        bound = vals - 0.5 * g_sq / theta
        tol = admm.backtrack_rtol * (jnp.abs(bound) + 1e-12)
        return bound + tol >= obj_lanes(x_new)

    def cond(carry):
        theta, done, it = carry
        return (~jnp.all(done)) & (it < admm.max_backtracks)

    def body(carry):
        theta, done, it = carry
        theta = jnp.where(done, theta, theta * admm.backtrack_growth)
        done = done | accepted(theta)
        return theta, done, it + 1

    theta0 = jnp.maximum(theta0 / admm.backtrack_growth, 1e-8)
    done0 = accepted(theta0)
    theta, _, iters = jax.lax.while_loop(cond, body,
                                         (theta0, done0, jnp.asarray(0)))
    return x - grads / theta[:, None, None], theta, probe_count(iters, admm)


def fista_lanes(admm: ADMMConfig, b, u, labels, mask, z_init, denom):
    """Eq. (7) per community lane: R(Z,Y_m) + ⟨U_m, Z−B_m⟩ + ρ/2‖Z−B_m‖².

    All arrays carry a leading lane dim k; each lane runs its own Lipschitz
    backtracking (lane-masked), so communities on the same device still
    solve their subproblems exactly as independent agents would.  Returns
    Z and the ``probe_count`` of its ``fista_iters`` searches, summed.
    """

    def obj_lanes(z):                                    # (k,) values
        logp = jax.nn.log_softmax(z, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        ce = jnp.sum(nll * mask, axis=1) / denom
        r = z - b
        lin = jnp.sum(u * r, axis=(1, 2))
        quad = 0.5 * admm.rho * jnp.sum(r * r, axis=(1, 2))
        return ce + lin + quad

    grad_fn = jax.grad(lambda z: obj_lanes(z).sum())

    def step(carry, _):
        z, y, t, lip = carry
        vals_y = obj_lanes(y)
        g = grad_fn(y)
        g_sq = jnp.sum(g * g, axis=(1, 2))

        def accepted(lip):
            z_new = y - g / lip[:, None, None]
            bound = vals_y - 0.5 * g_sq / lip
            tol = admm.backtrack_rtol * (jnp.abs(bound) + 1e-12)
            return obj_lanes(z_new) <= bound + tol

        def cond(carry):
            lip, done, it = carry
            return (~jnp.all(done)) & (it < admm.max_backtracks)

        def body(carry):
            lip, done, it = carry
            lip = jnp.where(done, lip, lip * admm.backtrack_growth)
            done = done | accepted(lip)
            return lip, done, it + 1

        lip, _, iters = jax.lax.while_loop(
            cond, body, (lip, accepted(lip), jnp.asarray(0)))
        z_new = y - g / lip[:, None, None]
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = z_new + ((t - 1.0) / t_new) * (z_new - z)
        return (z_new, y_new, t_new, lip * 0.9), probe_count(iters, admm)

    k = z_init.shape[0]
    init = (z_init, z_init, jnp.asarray(1.0),
            jnp.full((k,), admm.rho + 1.0))
    (z, _, _, _), probes = jax.lax.scan(step, init, None,
                                        length=admm.fista_iters)
    return z, probes.sum(axis=0)


# ---------------------------------------------------------------------------
# one ADMM iteration, per-shard body (k communities per shard)
# ---------------------------------------------------------------------------

def _iteration_body(cfg: gcn.GCNConfig, admm: ADMMConfig, use_kernel: bool,
                    comm_bf16: bool, compressed: bool,
                    plan: "messages.NeighborExchange | None",
                    overlap: bool, fused: bool,
                    packed_aux: "dict | None",
                    mb_aux: "dict | None",
                    agg_widths: list,
                    adj, nbr_row, z0_loc, ax_loc, labels_loc, mask_loc,
                    denom, ws, zs_loc, u_loc, taus, thetas, probes,
                    nbr_decay=None):
    """Shapes per shard: nbr_row (k,M); z*_loc (k,n,C); thetas[l] (k,);
    probes (1, len(probe_columns(L))), to which the round adds the
    ``probe_count`` of every line search it ran, summed and per search.

    ``ax_loc`` (k, n, C_0) is this shard's lanes of Ã·Z_0, which the
    trainer aggregates once at construction: Z_0 = X never changes, so
    every site that aggregates the layer-1 input (the W_1 search, the
    layer-1 Z target, and for L = 1 FISTA's B and the dual) reads it, and
    no round aggregates or gathers Z_0.  The one exception is the fused
    packed wire (``fused`` with a plan), whose Z sites keep their one-pass
    Ã(Z_0 W) and so gather Z_0 once a round; its W_1 search reads
    ``ax_loc`` too.

    ``agg_widths`` is cleared, then gets the width of each distinct
    gathered operand the round aggregates, as the body is traced: the W,
    Z and FISTA sites aggregate each Z_l (l >= 1) once between them (XLA
    merges the repeats into one call), and the dual aggregates its
    refresh.

    The four sub-updates run under ``jax.named_scope`` ``admm_w`` (Line 3
    with its line search), ``admm_z`` (eq. 5/6), ``admm_fista`` (eq. 7)
    and ``admm_dual`` (eq. 3), so each HLO op they lower to carries the
    scope in its ``op_name`` metadata, and device traces attribute op time
    to the sub-update.  Inside ``admm_w`` each layer's update runs under
    ``l1`` … ``lL``, inside ``admm_z`` each hidden layer's under ``l1`` …
    ``l{L-1}`` (``admm_w/l2``, ``admm_z/l1`` in the op's path).

    ``adj`` is the shard's adjacency rows — dense mode: a_row (k,M,n,n);
    compressed mode: (ell_rows (k,max_deg,n,n), ell_idx (k,max_deg),
    ell_msk (k,max_deg), ell_rcnt (k,), ell_ncnt (k,max_deg)) with the
    ragged row counts feeding the ELL kernel's pad-row guards.  ``plan``
    selects the transport: None means
    all-gather (ell_idx holds *global* community ids into the gathered
    (M,n,C) payload); a NeighborExchange means neighbour-only ppermute
    rounds (ell_idx is pre-remapped to slots of the (r_pad,n,C) receive
    buffer, and no (M,n,C) tensor exists in this body).

    ``packed_aux`` (packed state mode) is a dict of *static* host tables:
    z*_loc/u_loc arrive as this shard's Σ-bucket-rows planes, are
    rebuilt into the blocked views above via take-with-fill (bitwise
    lossless under the zero-outside-counts contract), and the updated
    Z/U are re-packed on exit.  With a plan, the exchange itself runs on
    the packed plane and the ELL aggregation reads the packed receive
    plane through per-slot row offsets; ``overlap`` further splits the
    aggregation by arrival round so each group's compute can overlap the
    later ppermute rounds.

    Every ``gather`` returns an ``(agg, blk)`` pair: ``agg`` feeds
    ``rowagg`` (the packed plane / its staged snapshots in packed mode)
    and ``blk`` is the blocked row view every other consumer indexes.
    Outside packed mode both elements are the same buffer.

    ``mb_aux`` (stochastic minibatching — requires packed + compressed)
    carries the *static* per-shard sample mask table of this compiled
    batch: ``smask[s, j]`` is 1.0 iff shard s's lane j is sampled this
    round (shard-granular, so a shard's lanes agree).  ``nbr_decay`` is
    the traced (k, max_deg) staleness weight d_r = stale_decay**age_r of
    each lane's stored neighbours (subproblems.stale_weights).  The body
    then (a) masks unsampled lanes' residuals out of the W-update psums,
    (b) scales every Z-coupling penalty to neighbour r by d_r — √d_r is
    folded into ``wt`` so the squared residuals carry the full weight,
    and the last layer's dual term gets the second √d_r explicitly —
    and (c) applies the Z/θ/U updates through a lane ``where`` so
    unsampled lanes keep their iterates bit-for-bit.  Every knob is
    exact-at-identity (mask 1.0, d 1.0 → multiplies by 1.0, selects of
    the new value), so a full batch reproduces the unsampled program
    bitwise.
    """
    f = gcn.activation_fn(cfg.activation)
    num_layers = cfg.num_layers
    m_total = nbr_row.shape[1]
    nbrf = nbr_row.astype(jnp.float32)           # (k, M) 1/0 neighbour rows
    # union of this shard's lanes' neighbourhoods: the only communities
    # whose payload rows any local subproblem reads
    shard_nbr = jnp.max(nbrf, axis=0)            # (M,)

    if mb_aux is not None:
        smask = jnp.asarray(mb_aux["smask"])[jax.lax.axis_index(AXIS)]
        smask_b = smask > 0                      # (k,) sampled lanes
        sm = smask[:, None, None]                # residual mask, (k,1,1)
        sdr = jnp.sqrt(nbr_decay)                # √d_r, (k, max_deg)
    else:
        smask_b = sm = sdr = None

    packed_wire = packed_aux is not None and plan is not None
    fused_wire = packed_wire and fused
    if packed_aux is not None:
        sid0 = jax.lax.axis_index(AXIS)
        kk, npd = packed_aux["k"], packed_aux["n"]
        unp_tbl = jnp.asarray(packed_aux["unpack"])[sid0]    # (k·n,)
        pk_tbl = jnp.asarray(packed_aux["pack"])[sid0]       # (plane_rows,)

        def from_plane(p):
            flat = jnp.take(p, unp_tbl, axis=0, mode="fill", fill_value=0)
            return flat.reshape((kk, npd) + p.shape[1:])

        def to_plane(blk):
            flat = blk.reshape((kk * npd,) + blk.shape[2:])
            return jnp.take(flat, pk_tbl, axis=0, mode="fill", fill_value=0)

        if fused_wire:
            z0_loc = from_plane(z0_loc)
        labels_loc = from_plane(labels_loc)
        mask_loc = from_plane(mask_loc)
        zs_loc = tuple(from_plane(z) for z in zs_loc)
        u_loc = from_plane(u_loc)

    if compressed:
        ell_rows, ell_idx, ell_msk, ell_rcnt, ell_ncnt = adj
        ell_f = ell_msk.astype(jnp.float32)      # (k, max_deg)
        if use_kernel:
            from repro.kernels import ops as kops

            def agg_blocked(zh):
                # scalar-prefetched indices steer the Z-block DMA; padding
                # slots skip via @pl.when and the row-count guards drop pad
                # rows of ragged (bucketed) layouts: work ∝ true block rows
                return kops.community_spmm_ell(ell_rows, ell_idx, ell_msk,
                                               zh, ell_rcnt, ell_ncnt)
        else:
            def agg_blocked(zh):         # Σ_{d} Ã[m,d] Z[idx[m,d]] per lane
                zg = zh[ell_idx] * ell_f[..., None, None]
                return jnp.einsum("kdip,kdpc->kic",
                                  ell_rows.astype(jnp.float32),
                                  zg.astype(jnp.float32))
    elif use_kernel:
        a_row = adj
        from repro.kernels import ops as kops

        def agg_blocked(zh):
            # per-lane neighbour rows engage the kernel's @pl.when block
            # skipping: work ∝ nnz blocks, not M²
            return kops.community_spmm(a_row, zh, nbr_row)
    else:
        a_row = adj

        def agg_blocked(zh):             # Σ_{r∈N_m} Ã_{m,r} Z_r per lane
            return jnp.einsum("kmip,mpc->kic",
                              a_row * nbrf[:, :, None, None], zh)

    if packed_wire:
        off_lanes = jnp.asarray(packed_aux["offsets"])[sid0]   # (k, D)
        lane_n = jnp.arange(npd)
        if use_kernel:
            from repro.kernels import ops as kops

            def agg_plane(plane, msk):
                # offset-indexed kernel: the Z DMA reads the packed
                # receive plane at the scalar-prefetched slot offsets
                return kops.community_spmm_ell_packed(
                    ell_rows, off_lanes, msk, plane, ell_rcnt, ell_ncnt)
        else:
            def agg_plane(plane, msk):
                rows = off_lanes[..., None] + lane_n[None, None, :]
                valid = (lane_n[None, None, :] < ell_ncnt[..., None]) \
                    & (msk[..., None] != 0)
                rows = jnp.where(valid, rows, plane.shape[0])
                zg = jnp.take(plane, rows.reshape(-1), axis=0,
                              mode="fill", fill_value=0)
                zg = zg.reshape(rows.shape + plane.shape[1:])
                return jnp.einsum("kdip,kdpc->kic",
                                  ell_rows.astype(jnp.float32),
                                  zg.astype(jnp.float32))

        if overlap:
            grp_lanes = jnp.asarray(packed_aux["groups"])[sid0]  # (k, D)

            def rowagg(x):
                # double-buffered schedule: stage g of the exchange holds
                # everything rounds < g delivered, so group g's partial
                # aggregation depends on no later ppermute — XLA is free
                # to run it while those rounds are still on the wire
                stages = x[0]
                acc = agg_plane(stages[0], ell_f * (grp_lanes == 0))
                for gi in range(1, len(stages)):
                    acc = acc + agg_plane(stages[gi],
                                          ell_f * (grp_lanes == gi))
                return acc
        else:
            def rowagg(x):
                return agg_plane(x[0], ell_f)
    else:
        def rowagg(x):
            return agg_blocked(x[0])

    # ``rowagg_mm(x, w)`` is the aggregation→GEMM composite the Z-update
    # sites consume.  Unfused it is literally ``rowagg(x) @ w`` (bitwise
    # the historic program); fused on the packed wire it runs the one-pass
    # kernel / the reassociated A·(Z·W) oracle, so the aggregated
    # (k, n, C_in) stack never exists outside VMEM scratch.  Overlap
    # composes by linearity: (Σ_g agg_g) @ W == Σ_g (agg_g @ W), each
    # arrival group's fused call depending only on its own stage buffer.
    if fused_wire:
        if use_kernel:
            from repro.kernels import ops as kops

            def agg_plane_mm(plane, msk, w):
                return kops.community_spmm_ell_fused(
                    ell_rows, off_lanes, msk, plane, w, ell_rcnt, ell_ncnt)
        else:
            def agg_plane_mm(plane, msk, w):
                # reassociated oracle: pre-multiplying the packed plane
                # keeps the compiled CPU program aggregate-free too
                return agg_plane(plane @ w, msk)

        if overlap:
            def rowagg_mm(x, w):
                stages = x[0]
                acc = agg_plane_mm(stages[0], ell_f * (grp_lanes == 0), w)
                for gi in range(1, len(stages)):
                    acc = acc + agg_plane_mm(stages[gi],
                                             ell_f * (grp_lanes == gi), w)
                return acc
        else:
            def rowagg_mm(x, w):
                return agg_plane_mm(x[0], ell_f, w)
    else:
        def rowagg_mm(x, w):
            return rowagg(x) @ w

    agg_widths.clear()
    agg_seen = []

    def tallied(agg):
        def run(x, *w):
            if not any(x is s for s in agg_seen):
                agg_seen.append(x)
                agg_widths.append(int(x[1].shape[-1]))
            return agg(x, *w)
        return run

    rowagg, rowagg_mm = tallied(rowagg), tallied(rowagg_mm)

    if packed_wire:
        ru_tbl = jnp.asarray(packed_aux["recv_unpack"])[sid0]  # (r_pad·n,)

        def gather(x_loc):
            """packed p2p: pack the blocked local rows onto this shard's
            plane, run the ppermute schedule on packed row payloads
            (byte-identical wire to the strided plan), and rebuild the
            (r_pad, n, C) blocked view for the row-indexed consumers."""
            plane = to_plane(x_loc)
            res = messages.exchange_neighbors_packed(
                plan, plane, AXIS, comm_bf16=comm_bf16, staged=overlap)
            buf = res[-1] if overlap else res
            flat = jnp.take(buf, ru_tbl, axis=0, mode="fill", fill_value=0)
            blk = flat.reshape((plan.r_pad, npd) + x_loc.shape[2:])
            return (res, blk)
    elif plan is not None:
        def gather(x_loc):
            """p2p transport: (k, n, C) local -> (r_pad, n, C) neighbour
            receive buffer via the static ppermute round schedule.  Only
            the rows this shard's subproblems read ever hit the wire (plus
            round padding); consumers index the buffer through the
            pre-localized ELL slots."""
            buf = messages.exchange_neighbors(plan, x_loc, AXIS,
                                              comm_bf16=comm_bf16)
            return (buf, buf)
    else:
        def gather(x_loc):
            """allgather transport: (k, n, C) local -> (M, n, C) global
            (community-major order), masked down to the rows
            r ∈ ∪_lanes N_m that this shard's subproblems actually read —
            the mask documents/verifies the needed volume the p2p transport
            realizes (``ParallelADMMTrainer.comm_stats``).

            With ``comm_bf16`` the paper's p/s message payloads travel in
            bf16 (half the collective bytes; §Perf; messages.bf16_wire) and
            are restored to f32 for the local subproblem math."""
            dt = x_loc.dtype
            gather_all = partial(jax.lax.all_gather, axis_name=AXIS)
            g = messages.bf16_wire(gather_all, x_loc) if comm_bf16 \
                else gather_all(x_loc)               # (n_shards, k, n, C)
            g = g.reshape((m_total,) + x_loc.shape[1:])
            g = g * shard_nbr[:, None, None].astype(dt)
            return (g, g)

    # gathered k-th iterates — one communication round per ADMM iteration.
    # Z_0 is static input, aggregated once at construction (``ax_loc``):
    # only the fused wire's one-pass Z sites gather it, once per step.
    zh0 = gather(z0_loc) if fused_wire else None
    zh = [gather(z) for z in zs_loc]            # Z_1..Z_L
    zh_in = [zh0] + zh[:-1]                     # layer inputs

    def agg_in_mm(l, w):
        """Ã·(layer l+1's input)·w at a Z-update site: (Ã Z_0) w from
        ``ax_loc`` for the first layer, off the fused wire."""
        if l == 0 and not fused_wire:
            return ax_loc @ w
        return rowagg_mm(zh_in[l], w)

    # ---- Line 3: W update (layer-parallel, Jacobi over Z^k) ----
    new_ws, new_taus, counts = [], [], []
    with jax.named_scope("admm_w"):
        for l in range(num_layers):
            with jax.named_scope(f"l{l + 1}"):
                # (k, n, C_{l-1})
                agg = ax_loc if l == 0 else rowagg(zh_in[l])

                # minibatch: unsampled lanes' constraints leave the
                # (psum-ed) W objective entirely — their residuals mask to
                # exact zeros
                if l < num_layers - 1:
                    def local_obj(w, agg=agg, z=zs_loc[l]):
                        r = z - f(agg @ w)
                        if sm is not None:
                            r = r * sm
                        return 0.5 * admm.nu * jnp.vdot(r, r).real
                else:
                    def local_obj(w, agg=agg, z=zs_loc[l]):
                        r = z - agg @ w
                        if sm is not None:
                            r = r * sm
                        return jnp.vdot(u_loc, r).real + \
                            0.5 * admm.rho * jnp.vdot(r, r).real
                w_new, tau, n = backtracking_step_psum(local_obj, ws[l],
                                                       taus[l], admm)
                new_ws.append(w_new)
                new_taus.append(tau)
                counts.append(n)

    # ---- Line 4: Z update (community-parallel, reads W^{k+1}, Z^k) ----
    new_zs, new_thetas = [], []
    with jax.named_scope("admm_z"):
        for l in range(1, num_layers):              # hidden layers (eq. 5/6)
            with jax.named_scope(f"l{l}"):
                w_l, w_next = new_ws[l - 1], new_ws[l]
                target1 = f(agg_in_mm(l - 1, w_l))           # (k, n, C_l)
                # relay aggregates q_{l,r} (eq. 4 second-order payload),
                # all r
                q_loc = rowagg_mm(zh[l - 1], w_next)         # (k, n, C_next)
                q_all = gather(q_loc)[1]                     # blocked rows
                z_ref = zs_loc[l - 1]

                # Coupling term of ψ (paper eq. 5/6): every neighbour
                # community r's next-layer pre-activation as a function of
                # my lanes,
                #   pre[j, r] = q_r + Ã_{r,m_j} (z_j − z_ref_j) W.
                # Lane m's ψ only sums r ∈ N_m ∪ {m} — the r ∉ N_m
                # residuals are constants in z (zero gradient) and drop
                # from the objective.
                if compressed:
                    # neighbour-compressed form: enumerate the max_deg
                    # stored neighbours only.  Ã_{r,m} = Ã_{m,r}ᵀ (Ã
                    # symmetric), so the stored row blocks are consumed
                    # transposed ("kdnp,knc->kdpc") — the gather-transpose
                    # trick of second_order_from_relay.
                    # O(max_deg·n_pad²·C) per lane instead of the dense
                    # O(M·…).
                    def pre_nbr(z, q_all=q_all, z_ref=z_ref,
                                w_next=w_next):
                        delta = (z - z_ref) @ w_next         # (k, n, C)
                        own = jnp.einsum("kdnp,knc->kdpc",
                                         ell_rows.astype(jnp.float32),
                                         delta)
                        return q_all[ell_idx] + own          # (k, D, n, C)

                    # staleness damping: √d_r folded into the coupling
                    # weight, so every squared residual carries the full
                    # d_r (exact identity when all ages are 0: ell_f · 1.0
                    # is bitwise ell_f)
                    wt = (ell_f * sdr if sdr is not None
                          else ell_f)[..., None, None]       # (k, D, 1, 1)

                    def nbr_vals(x_all):
                        """(M, n, C) gathered payload -> this lane's
                        (k, D, n, C)."""
                        return x_all[ell_idx]
                else:
                    def pre_nbr(z, q_all=q_all, z_ref=z_ref,
                                w_next=w_next):
                        delta = (z - z_ref) @ w_next         # (k, n, C)
                        return q_all[None] + jnp.einsum("kmnp,knc->kmpc",
                                                        a_row, delta)

                    wt = nbrf[:, :, None, None]              # (k, M, 1, 1)

                    def nbr_vals(x_all):
                        return x_all[None]                   # (1, M, n, C)

                if l + 1 < num_layers:
                    zh_next = zh[l][1]

                    def obj_lanes(z, target1=target1, pre_nbr=pre_nbr,
                                  zh_next=zh_next):
                        r1 = z - target1
                        v1 = 0.5 * admm.nu * jnp.sum(r1 * r1, axis=(1, 2))
                        r2 = (nbr_vals(zh_next) - f(pre_nbr(z))) * wt
                        v2 = 0.5 * admm.nu * jnp.sum(r2 * r2,
                                                     axis=(1, 2, 3))
                        return v1 + v2
                else:
                    zh_last, uh = zh[l][1], gather(u_loc)[1]

                    def obj_lanes(z, target1=target1, pre_nbr=pre_nbr,
                                  zh_last=zh_last, uh=uh):
                        r1 = z - target1
                        v1 = 0.5 * admm.nu * jnp.sum(r1 * r1, axis=(1, 2))
                        r2 = (nbr_vals(zh_last) - pre_nbr(z)) * wt
                        uv = nbr_vals(uh)
                        if sdr is not None:
                            # second √d_r: r2 carries one, so the dual
                            # term ⟨U_r, ·⟩ scales by the full staleness
                            # weight d_r
                            uv = uv * sdr[..., None, None]
                        lin = jnp.sum(uv * r2, axis=(1, 2, 3))
                        quad = 0.5 * admm.rho * jnp.sum(r2 * r2,
                                                        axis=(1, 2, 3))
                        return v1 + lin + quad

                z_new, theta, n = backtracking_step_lanes(
                    obj_lanes, zs_loc[l - 1], thetas[l - 1], admm)
                counts.append(n)
                if smask_b is not None:
                    # unsampled lanes keep their iterates bit-for-bit
                    # (exact block-coordinate step on the sampled blocks)
                    z_new = jnp.where(smask_b[:, None, None], z_new,
                                      zs_loc[l - 1])
                    theta = jnp.where(smask_b, theta, thetas[l - 1])
                new_zs.append(z_new)
                new_thetas.append(theta)

    # ---- Z_L: per-community FISTA prox (eq. 7) ----
    with jax.named_scope("admm_fista"):
        b = agg_in_mm(num_layers - 1, new_ws[-1])
        z_last, n = fista_lanes(admm, b, u_loc, labels_loc, mask_loc,
                                zs_loc[-1], denom)
        counts.append(n)
        if smask_b is not None:
            z_last = jnp.where(smask_b[:, None, None], z_last, zs_loc[-1])
        new_zs.append(z_last)
        new_thetas.append(thetas[-1])

    # ---- Line 5: dual ascent (eq. 3) with updated iterates ----
    with jax.named_scope("admm_dual"):
        # wide-first, (Ã Z⁺) W⁺: the association the next round's W_L line
        # search forms its residual with.  Ã(Z⁺ W⁺) differs from it at
        # float32 rounding, and on a v5e at the paper's widths that
        # stalled the search (τ_L doubled to its cap) on every seed tried
        if num_layers >= 2:
            b_new = rowagg_mm(gather(new_zs[num_layers - 2]), new_ws[-1])
        else:
            b_new = agg_in_mm(0, new_ws[-1])
        new_u = u_loc + admm.rho * (new_zs[-1] - b_new)
        if smask_b is not None:
            new_u = jnp.where(smask_b[:, None, None], new_u, u_loc)

    if packed_aux is not None:
        # carry state between steps in the packed plane — the blocked
        # (k, n, C) iterates never leave this body
        new_zs = [to_plane(z) for z in new_zs]
        new_u = to_plane(new_u)

    # the round's counts summed over its searches, then each search's own
    # (``probe_columns``)
    round_counts = jnp.concatenate([sum(counts)] + counts)
    return (tuple(new_ws), tuple(new_zs), new_u,
            tuple(new_taus), tuple(new_thetas), probes + round_counts[None])


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

class ParallelADMMTrainer:
    """The paper's 'Parallel ADMM': M community agents on a device mesh."""

    def __init__(self, cfg: gcn.GCNConfig, admm: ADMMConfig, g: graph.Graph,
                 num_parts: int, mesh: Mesh | None = None, seed: int = 0,
                 config: TrainerConfig | None = None,
                 part: np.ndarray | None = None,
                 **legacy_flags):
        if legacy_flags:
            unknown = sorted(set(legacy_flags) - set(_LEGACY_FLAGS))
            if unknown:
                raise TypeError(
                    f"ParallelADMMTrainer got unexpected keyword arguments "
                    f"{unknown}; pass config=TrainerConfig(...)")
            if config is not None:
                raise ValueError(
                    "pass either config=TrainerConfig(...) or the legacy "
                    "flag kwargs, not both")
            warnings.warn(
                "ParallelADMMTrainer flag kwargs are deprecated; pass "
                "config=TrainerConfig(...) instead",
                DeprecationWarning, stacklevel=2)
            config = TrainerConfig(**legacy_flags)
        elif config is None:
            config = TrainerConfig()
        # all cross-flag validation lives in TrainerConfig.__post_init__
        self.config = config
        self.cfg, self.admm, self.graph = cfg, admm, g
        self.compressed = compressed = config.compressed
        self.transport = transport = config.transport
        self.packed = packed = config.packed
        self.overlap = overlap = config.overlap
        self.fused = fused = config.fused
        self.pad_mode = pad_mode = config.pad_mode
        use_kernel = config.use_kernel
        comm_bf16 = config.comm_bf16
        adjacency_bf16 = config.adjacency_bf16
        partitioner = config.partitioner
        if part is None:
            partitioner = partitioner or "bfs_kl"
            part = graph.partition_graph(g.num_nodes, g.edges, num_parts,
                                         seed=seed, method=partitioner)
        else:
            # caller-supplied partition; a caller that computed it with
            # partition_graph may pass ``partitioner`` so the stats stay
            # honestly labelled (no re-partition just for the tag)
            partitioner = partitioner or "precomputed"
        self.partitioner = partitioner
        # the host spans ``construct.layout`` and ``construct.init_state``
        # split set-up between the blocked layout with its device data and
        # the initial state
        with spans.span("construct.layout"):
            self.partition_stats = graph.partition_quality(
                g.num_nodes, g.edges, part, num_parts)
            self.layout = graph.build_community_layout(
                g.num_nodes, g.edges, part, compressed=compressed,
                pad_mode=pad_mode)
            m = int(np.asarray(self.layout.neighbor_mask).shape[0])

            if mesh is None:
                n_dev = len(jax.devices())
                n_shards = max(d for d in range(1, n_dev + 1) if m % d == 0)
                mesh = jax.make_mesh((n_shards,), (AXIS,), (AxisType.Auto,),
                                     devices=jax.devices()[:n_shards])
            self.mesh = mesh
            n_shards = mesh.shape[AXIS]

            # packed state: each shard's Z/U/z0/label rows live back to
            # back at their bucket row counts on a flat plane — resident
            # bytes track true community size, not M·n_pad
            # (docs/layout.md)
            self.packed_layout = self.layout.device_layout(n_shards) \
                if packed else None
            # every data array starts where the step reads it: lane-major
            # rows split over the comm axis, the scalar denominator
            # replicated
            data = community_data(g, self.layout, compressed=compressed,
                                  adjacency_bf16=adjacency_bf16,
                                  device_layout=self.packed_layout)
            self.data = dataclasses.replace(data, **{
                f.name: place_on_mesh(mesh, getattr(data, f.name),
                                      P() if f.name == "denom" else P(AXIS))
                for f in dataclasses.fields(data)
                if f.name != "packed_layout"
                and getattr(data, f.name) is not None})

        with spans.span("construct.init_state"):
            # init from the same forward pass as the serial trainer
            ws = gcn.init_weights(cfg, jax.random.key(seed))
            a_full = graph.normalized_adjacency(g.num_nodes, g.edges)
            zs_full = gcn.forward(cfg, jnp.asarray(a_full),
                                  jnp.asarray(g.features), ws)
            if packed:
                dl = self.packed_layout
                zs = tuple(dl.pack_state(self.layout.pack(np.asarray(z)))
                           for z in zs_full)
            else:
                zs = tuple(self.layout.pack(np.asarray(z)) for z in zs_full)
            del zs_full
            u = np.zeros_like(zs[-1])
            taus = tuple(jnp.asarray(admm.tau_init) for _ in ws)
            thetas = tuple(jnp.full((m,), admm.tau_init) for _ in zs)
            probes = np.zeros((n_shards, len(probe_columns(cfg.num_layers))),
                              np.int32)
            sharded, rep = P(AXIS), P()
            n_l = cfg.num_layers
            self.state_spec = ParallelState((rep,) * n_l, (sharded,) * n_l,
                                             sharded, (rep,) * n_l,
                                             (sharded,) * n_l, sharded)
            # the state starts where the step's shard_map reads it, spread
            # over the comm axis — not all on the default device
            self.state = place_on_mesh(mesh, ParallelState(
                tuple(ws), zs, u, taus, thetas, probes), self.state_spec)

        self._plan = None
        ell_idx_dev = self.data.ell_indices
        if self.transport == "p2p":
            # bucketed layouts wire row-exact payloads: only each wired
            # community's true rows ever cross the wire; the global scheme
            # keeps the historic whole-n_pad-block messages.  Packed mode
            # additionally threads bucket row_counts so the plan carries
            # the plane routing tables (send/recv packed rows, offsets).
            self._plan = messages.build_neighbor_exchange(
                self.layout.neighbor_mask, n_shards, self.layout.n_pad,
                sizes=self.layout.sizes if pad_mode == "bucketed" else None,
                row_counts=self.layout.eff_row_counts() if packed else None)
            if n_shards == 1:
                # one shard hosts every community: nothing ever crosses the
                # wire, the transports are the same program (the all-gather
                # is a no-op collective), so keep the well-tested gather
                # body and only the p2p byte accounting (wire_bytes == 0)
                body_plan = None
            else:
                # ELL indices remapped host-side to receive-buffer slots —
                # the body never sees an (M, ...) payload
                body_plan = self._plan
                csr = self.layout.compress()
                ell_idx_dev = np.asarray(self._plan.localize_indices(
                    csr.ell_indices, csr.ell_mask))
        else:
            body_plan = None

        # static host tables for the packed body — captured in the partial
        # and indexed in-body by axis_index, so the shard_map specs never
        # see them (same pattern as the plan's send/recv tables)
        overlap_on = bool(overlap and body_plan is not None)
        fused_on = bool(fused and body_plan is not None)
        packed_aux = None
        if packed:
            dl = self.packed_layout
            packed_aux = {
                "k": int(dl.lanes_per_shard),
                "n": int(dl.n_pad),
                "unpack": np.asarray(dl.unpack_rows),
                "pack": np.asarray(dl.pack_rows),
            }
            if body_plan is not None:
                csr = self.layout.compress()
                packed_aux["recv_unpack"] = \
                    np.asarray(self._plan.recv_unpack_rows)
                packed_aux["offsets"] = np.asarray(
                    self._plan.localized_offsets(
                        csr.ell_indices, csr.ell_mask)).reshape(
                    n_shards, dl.lanes_per_shard, -1)
                if overlap_on:
                    # host tables the per-step arrival-group computation
                    # needs: slot layout is plan-stable (restrict_exchange
                    # never touches buffer geometry), so the localized
                    # slots are computed once against the full plan
                    ov_loc = np.asarray(self._plan.localize_indices(
                        csr.ell_indices, csr.ell_mask)).reshape(
                        n_shards, dl.lanes_per_shard, -1)
                    ov_msk = np.asarray(csr.ell_mask).reshape(
                        n_shards, dl.lanes_per_shard, -1)

        if compressed:
            # each shard carries only its lanes' ELL rows — no dense
            # (M, M, n_pad, n_pad) tensor exists on device — plus its
            # lanes' ragged row counts for the kernel pad-row guards
            adj_data = (self.data.ell_blocks, ell_idx_dev,
                        self.data.ell_mask, self.data.row_counts,
                        self.data.nbr_counts)
            adj_spec = (sharded, sharded, sharded, sharded, sharded)
        else:
            adj_data = self.data.a_blocks
            adj_spec = sharded
        # full-M packed aggregation: ELL for Ã·Z_0 in every mode and for the
        # metrics/Lagrangian programs in compressed mode (no dense
        # adjacency is retained on device), the masked dense einsum for
        # dense mode's.  The ELL aggregation runs per shard under the mesh
        # (a Pallas kernel cannot be auto-partitioned across chips): each
        # shard aggregates its own lanes against the replicated blocked Z.
        # ``use_kernel`` picks the kernel or the einsum, as in the step
        # body.
        from repro.kernels import ops as kops
        from repro.kernels import ref as kref
        agg_lanes = kops.community_spmm_ell if use_kernel \
            else kref.community_spmm_ell_einsum
        agg_ell = jax.shard_map(
            lambda ell, z: agg_lanes(*ell[:3], z, *ell[3:]), mesh=mesh,
            in_specs=((sharded,) * 5, rep), out_specs=sharded,
            check_vma=False)
        data = self.data
        if compressed:
            agg_full = agg_ell
            adj_full = (data.ell_blocks, data.ell_indices, data.ell_mask,
                        data.row_counts, data.nbr_counts)
            ell_full = adj_full
        else:
            adj_full = (data.a_blocks, data.neighbor_mask)

            def agg_full(adj, z_pack):
                a_blocks, nbr = adj
                nbr_f = nbr.astype(jnp.float32)
                return jnp.einsum("mrip,rpc->mic",
                                  a_blocks * nbr_f[:, :, None, None], z_pack)

            # the ELL view of the dense blocks, for Ã·Z_0 alone
            csr = self.layout.compress()
            ell_full = place_on_mesh(mesh, (
                csr.ell_blocks, csr.ell_indices, csr.ell_mask,
                *csr.ell_row_counts()), sharded)

        # Ã·Z_0 and the eval programs run on the blocked (M, n_pad, ...)
        # view; in packed mode the planes are rebuilt through the device
        # layout's global row table (take-with-fill, bitwise lossless
        # under the zero-outside-counts contract)
        if packed:
            gup = np.asarray(self.packed_layout.global_unpack_rows())
            n_pad_loc = self.layout.n_pad

            def unfold(p):
                flat = jnp.take(p, gup, axis=0, mode="fill", fill_value=0)
                return flat.reshape((m, n_pad_loc) + p.shape[1:])
        else:
            def unfold(p):
                return p

        # Z_0 = X never changes, so Ã·Z_0 is aggregated once, here, and
        # carried as device data: blocked (M, n_pad, C_0), split over the
        # comm axis like the step's lanes.  The step's first-layer sites
        # and both eval programs read it.  Every mode aggregates it through
        # the ELL view, so dense and compressed trainers start from the
        # same bits: the first W search's objective is float32 rounding
        # at construction (Z_1 is the forward pass's), and which way it
        # goes follows those bits.  The width of every distinct
        # aggregation each program lowers, filled as the program is traced
        # (``comm_stats["aggregations"]``), so C_0 is under "construct"
        agg_widths = {"construct": [int(data.z0.shape[-1])], "step": [],
                      "metrics": [], "lagrangian": []}
        with spans.span("construct.input_agg"):
            @partial(jax.jit, out_shardings=NamedSharding(mesh, sharded))
            def input_agg(ell, z0):
                return agg_ell(ell, unfold(z0))

            self.ax = jax.block_until_ready(input_agg(ell_full, data.z0))

        k_lanes = m // n_shards
        step_spec = (adj_spec, sharded, sharded, sharded, sharded, sharded,
                     rep)
        step_data = place_on_mesh(mesh, (
            adj_data, data.neighbor_mask, data.z0, self.ax, data.labels,
            data.train_mask, data.denom), step_spec)

        def make_step(sampled=None):
            """Compile one ADMM step.  ``sampled`` (an iterable of shard
            ids) builds the stochastic-minibatch variant: the p2p round
            schedule is restricted to messages whose destination shard is
            sampled (messages.restrict_exchange — unsampled shards send
            their stale-but-exact rows, receive nothing), a static lane
            mask bakes the batch into the program, and a traced
            (M, max_deg) staleness weight rides along as the single extra
            input.  One program per distinct shard batch; the sampler's
            cycle structure bounds the program count by ``num_batches``."""
            if sampled is None:
                step_plan, mb_aux = body_plan, None
            else:
                sampled = frozenset(int(s) for s in sampled)
                step_plan = body_plan if body_plan is None else \
                    messages.restrict_exchange(body_plan, sampled)
                smask = np.zeros((n_shards, k_lanes), dtype=np.float32)
                smask[sorted(sampled)] = 1.0
                mb_aux = {"smask": smask}
            step_aux = packed_aux
            if overlap_on:
                # ELL slot -> arrival group of the *active* schedule:
                # 0 = resident own lanes (aggregable before any wire),
                # g = delivered by this plan's ppermute round g-1.  A
                # restricted sub-plan delivers fewer slots (and possibly
                # fewer rounds) than the full plan, so the table is
                # derived per compiled batch — slots the sub-schedule
                # never delivers fall into group 0, aggregate the
                # own-copy stage's zero rows, and only reach unsampled
                # lanes' iterates, which the smask gates freeze anyway.
                arr = messages.arrival_rounds(step_plan)
                grp = np.zeros_like(ov_loc)
                for s in range(n_shards):
                    grp[s] = np.where(ov_msk[s] != 0,
                                      arr[s][ov_loc[s]] + 1, 0)
                step_aux = dict(packed_aux, groups=grp)
            body = partial(_iteration_body, cfg, admm, use_kernel,
                           comm_bf16, compressed, step_plan, overlap_on,
                           fused, step_aux, mb_aux, agg_widths["step"])
            in_specs = step_spec + tuple(self.state_spec)
            out_specs = tuple(self.state_spec)
            if mb_aux is not None:
                in_specs = in_specs + (sharded,)
            mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False)

            # the state rebinds every step: donating it lets XLA reuse the
            # Z/U/weight buffers in place instead of doubling peak HBM
            # (memory/donated-inputs proves this holds on the compiled step)
            if mb_aux is None:
                @partial(jax.jit, donate_argnums=(1,))
                def step(dev, state: ParallelState):
                    return ParallelState(*mapped(*dev, *state))
            else:
                @partial(jax.jit, donate_argnums=(1,))
                def step(dev, state: ParallelState, nbr_decay):
                    return ParallelState(*mapped(*dev, *state, nbr_decay))
            return BoundProgram(step, step_data), step_plan

        self._make_step = make_step
        self._sampler = None
        self._round = 0
        if config.batch_fraction is None:
            self._step, _ = make_step(None)
            self._active_plan = self._plan
        else:
            # shard batch weights = Σ bucket rows hosted, so the greedy
            # balance targets resident/wire work, not shard count alone
            rc_shard = np.asarray(self.layout.eff_row_counts(),
                                  dtype=np.float64).reshape(
                n_shards, k_lanes).sum(axis=1)
            self._sampler = CommunityBatchSampler(
                n_shards, config.batch_fraction,
                seed=config.sample_seed, weights=rc_shard)
            csr_mb = self.layout.compress()
            self._mb_nbr = np.asarray(csr_mb.ell_indices)  # (M, D) global
            self._mb_k = k_lanes
            self._ages = np.zeros(m, dtype=np.int64)
            self._mb_steps = {}
            batch0 = frozenset(self._sampler.batch(0))
            self._mb_steps[batch0] = make_step(batch0)
            self._step, plan0 = self._mb_steps[batch0]
            self._active_plan = plan0 if plan0 is not None else self._plan

        # collective volume per iteration: one (M, n_pad, C) payload per
        # gather of the body (``gathered_widths``; Z_0 on the fused wire
        # only)
        dims = list(cfg.layer_dims)
        gathered_cs = gathered_widths(dims, fused=fused_on)
        self.comm_stats = messages.gather_bytes(
            self.layout.neighbor_mask, self.layout.n_pad, gathered_cs,
            itemsize=2 if comm_bf16 else 4)
        self.comm_stats["transport"] = self.transport
        self.comm_stats["aggregations"] = agg_widths
        # residual-padding accounting: how many payload rows / aggregation
        # FLOPs this trainer spends beyond the true community sizes.  The
        # bucketed row_counts only shrink what a consumer actually
        # exploits, so each axis is gated on its consumer being engaged —
        # pad FLOPs drop only on the guarded-kernel path (use_kernel:
        # tiles past the row counts skip the DMA+accumulate on TPU; the
        # CPU/interpret fallbacks emulate the same masked semantics, so
        # off-TPU the number is the kernel-path bound rather than a
        # measured skip, while the default einsum body processes every
        # n_pad row and claims nothing), pad wire rows only under the
        # row-exact p2p transport (an all-gather moves full-pad payloads
        # regardless of layout) — the recorded numbers describe the
        # configured program, not the layout's potential
        self.comm_stats["pad_mode"] = pad_mode
        kernel_ragged = compressed and use_kernel
        wire_ragged = self.transport == "p2p"
        item = 2 if comm_bf16 else 4
        ps_flops = messages.pad_stats(
            self.layout.neighbor_mask, self.layout.sizes,
            self.layout.row_counts if kernel_ragged else None,
            self.layout.n_pad, gathered_cs, itemsize=item)
        ps_wire = messages.pad_stats(
            self.layout.neighbor_mask, self.layout.sizes,
            self.layout.row_counts if wire_ragged else None,
            self.layout.n_pad, gathered_cs, itemsize=item)
        self.comm_stats.update(ps_wire)
        self.comm_stats.update({k: ps_flops[k] for k in
                                ("pad_flops", "agg_flops", "pad_flop_frac")})
        self.comm_stats["pad_guards"] = {"kernel": kernel_ragged,
                                         "wire": wire_ragged}
        # the partition sets the communication: its edge cut is the p2p
        # wire volume's block count, its max_deg the ELL fan-in
        self.comm_stats["partitioner"] = self.partitioner
        self.comm_stats["partition"] = dict(self.partition_stats)
        if self._plan is not None:
            # scheduled p2p wire volume, tied to the mask-derived stats by
            # the transport invariant: wire == true rows + round padding
            # ≤ full, true rows ≤ needed (wire ≤ needed strictly at k=1)
            self.comm_stats.update(messages.exchange_bytes(
                self._plan, gathered_cs, itemsize=2 if comm_bf16 else 4))
            messages.verify_transport_bytes(self.comm_stats)
        else:
            # an all-gather moves every row to every shard
            self.comm_stats["wire_bytes"] = self.comm_stats["full_bytes"]
        # device-resident adjacency accounting for this trainer's mode
        # (itemsize-aware: the bf16 ELL block store halves the block term)
        self.comm_stats["adjacency"] = messages.adjacency_bytes(
            self.layout.neighbor_mask, self.layout.n_pad,
            itemsize=2 if adjacency_bf16 else 4)
        self.comm_stats["adjacency"]["resident_bytes"] = \
            int(self.data.adjacency_nbytes)

        # device-resident iterate accounting: the packed plane prices
        # Z/U/z0/labels/masks at Σ bucket rows (× the shard-max factor);
        # the strided layout at M·n_pad rows regardless of skew.  All
        # resident iterates are f32 (comm_bf16 compresses the wire only).
        z_cols = sum(dims[1:])                    # Z_1..Z_L feature columns
        state_cols = dims[0] + z_cols + dims[-1]  # + z0 + U
        rc_eff = np.asarray(self.layout.eff_row_counts(), dtype=np.int64)
        strided_rows = m * self.layout.n_pad
        resident_rows = self.packed_layout.total_rows if packed \
            else strided_rows
        self.comm_stats["state"] = {
            "packed": packed,
            "itemsize": 4,
            "rows": int(resident_rows),
            "strided_rows": int(strided_rows),
            "bucket_rows": int(rc_eff.sum()),
            "node_rows": int(np.asarray(self.layout.sizes).sum()),
            "z_bytes": int(resident_rows * z_cols * 4),
            "z_strided_bytes": int(strided_rows * z_cols * 4),
            "resident_bytes": int(resident_rows * (state_cols + 3) * 4),
            "strided_equiv_bytes": int(strided_rows * (state_cols + 3) * 4),
        }
        if self._plan is not None:
            # analytic overlap efficiency of the *active* round schedule —
            # consumed by benchmarks.roofline's exposed-wire pricing.
            # Under minibatching the compiled step runs the restricted
            # sub-plan, so that is what gets priced (the full plan would
            # overstate a sampled round's wire); ``step()`` re-prices when
            # the active batch changes.
            def _overlap_pricing(plan):
                return messages.overlap_stats(
                    plan, self.layout.neighbor_mask, gathered_cs,
                    itemsize=2 if comm_bf16 else 4, enabled=overlap_on)
            self._overlap_pricing = _overlap_pricing
            self.comm_stats["overlap"] = _overlap_pricing(self._active_plan)
        if self._sampler is None:
            self.comm_stats["minibatch"] = {"enabled": False}
        else:
            # sampled-round accounting over the first sampler cycle: every
            # batch's restricted schedule is priced with the same
            # exchange_bytes the full plan uses, so the wire ratio is an
            # apples-to-apples sub-plan/plan comparison
            cyc = self._sampler.cycle(0)
            wires, rows = [], []
            rc_sh = np.asarray(self.layout.eff_row_counts(),
                               dtype=np.int64).reshape(n_shards, k_lanes)
            for b in cyc:
                sub = self._plan if len(b) == n_shards else \
                    messages.restrict_exchange(self._plan, frozenset(b))
                wires.append(int(messages.exchange_bytes(
                    sub, gathered_cs, itemsize=item)["wire_bytes"]))
                rows.append(int(rc_sh[list(b)].sum()))
            self.comm_stats["minibatch"] = {
                "enabled": True,
                "batch_fraction": float(config.batch_fraction),
                "stale_decay": float(config.stale_decay),
                "sample_seed": int(config.sample_seed),
                "num_batches": int(self._sampler.num_batches),
                "schedule": [list(b) for b in cyc],
                "sampled_wire_bytes": wires[0],
                "mean_sampled_wire_bytes": float(np.mean(wires)),
                "full_wire_bytes": int(self.comm_stats["wire_bytes"]),
                "sampled_state_rows": rows[0],
                "mean_sampled_state_rows": float(np.mean(rows)),
                "full_state_rows": int(rc_sh.sum()),
            }

        # Each Ã·Z·W of the two programs aggregates at the narrower side
        # (``narrow_first_agg``), seeded with (Z_0, Ã·Z_0) so the first
        # layer is a GEMM on ``ax``
        f_act = gcn.activation_fn(cfg.activation)

        # the device data the host-side metrics read, bound as arguments
        # (never baked into the programs as constants)
        eval_data = {"adj": adj_full, "z0": data.z0, "ax": self.ax,
                     "labels": data.labels,
                     "train": data.train_mask, "test": data.test_mask,
                     "row_mask": data.row_mask, "denom": data.denom}

        def blocked(dev):
            return (unfold(dev["z0"]), unfold(dev["labels"]),
                    unfold(dev["train"]), unfold(dev["test"]),
                    dev["row_mask"][..., None])   # (M, n_pad, 1) true rows

        @jax.jit
        def metrics(dev, state: ParallelState):
            z0_blk, labels_blk, train_blk, test_blk, row_mask = blocked(dev)
            agg_mm = narrow_first_agg(partial(agg_full, dev["adj"]),
                                      agg_widths["metrics"],
                                      seeds=[(z0_blk, dev["ax"])])
            # community-blocked forward pass — logits (M, n_pad, C_L)
            logits = z0_blk
            for l, w in enumerate(state.weights):
                logits = agg_mm(logits, w)
                if l < cfg.num_layers - 1:
                    logits = f_act(logits)
            z_pen = unfold(state.zs[-2]) if cfg.num_layers >= 2 else z0_blk
            res = (unfold(state.zs[-1]) - agg_mm(z_pen, state.weights[-1])) \
                * row_mask
            return (gcn.accuracy(logits, labels_blk, train_blk),
                    gcn.accuracy(logits, labels_blk, test_blk),
                    jnp.linalg.norm(res))

        self._metrics = BoundProgram(metrics, eval_data)

        @jax.jit
        def lagrangian(dev, state: ParallelState):
            """ℒ_ρ(W, Z, U) — eq. (1) on the packed iterates.  Every
            residual is masked down to the true community rows
            (``row_mask``): pad slots carry zero adjacency/labels so the
            mask changes no value, it pins the invariant that padding —
            global or bucketed — never leaks into the objective, and the
            result equals the global subproblems.lagrangian_value on the
            unpacked state."""
            z0_blk, labels_blk, train_blk, _, row_mask = blocked(dev)
            ws = state.weights
            zs = tuple(unfold(z) for z in state.zs)
            u = unfold(state.u)
            logp = jax.nn.log_softmax(zs[-1], axis=-1)
            nll = -jnp.take_along_axis(logp, labels_blk[..., None],
                                       axis=-1)[..., 0]
            val = jnp.sum(nll * train_blk) / dev["denom"]
            agg_mm = narrow_first_agg(partial(agg_full, dev["adj"]),
                                      agg_widths["lagrangian"],
                                      seeds=[(z0_blk, dev["ax"])])
            z_prev = z0_blk
            for l in range(cfg.num_layers - 1):
                r = (zs[l] - f_act(agg_mm(z_prev, ws[l]))) * row_mask
                val += 0.5 * admm.nu * jnp.vdot(r, r).real
                z_prev = zs[l]
            r = (zs[-1] - agg_mm(z_prev, ws[-1])) * row_mask
            val += jnp.vdot(u * row_mask, r).real \
                + 0.5 * admm.rho * jnp.vdot(r, r).real
            return val

        self._lagrangian = BoundProgram(lagrangian, eval_data)

    def _nbr_decay(self):
        """Per-ELL-slot staleness weight d_r = stale_decay**age_r, looked
        up by the *global* neighbour community id (the body's localized
        indices never see community ids, so the table is built host-side
        and traced in as the step's one extra input)."""
        d = stale_weights(self._ages, self.config.stale_decay)
        return d[self._mb_nbr]                            # (M, max_deg)

    def _step_for(self, shards: frozenset):
        entry = self._mb_steps.get(shards)
        if entry is None:
            entry = self._make_step(shards)
            self._mb_steps[shards] = entry
        return entry

    @property
    def _analysis_args(self):
        """Arguments the compiled ``_step`` is lowered with (analysis)."""
        if self._sampler is None:
            return (self.state,)
        return (self.state, self._nbr_decay())

    def step(self) -> None:
        if self._sampler is None:
            self.state = self._step(self.state)
            return
        shards = frozenset(self._sampler.batch(self._round))
        step_fn, plan = self._step_for(shards)
        self._step = step_fn
        self._active_plan = plan if plan is not None else self._plan
        if "overlap" in self.comm_stats:
            # keep the overlap pricing tied to the plan this round runs
            self.comm_stats["overlap"] = self._overlap_pricing(
                self._active_plan)
        self.state = step_fn(self.state, self._nbr_decay())
        # ages advance after the round: a community sampled this round
        # ends it fresh (age 0 — "reset on resample"), everyone else's
        # consensus terms are one round staler
        self._ages += 1
        k = self._mb_k
        for s in shards:
            self._ages[s * k:(s + 1) * k] = 0
        self._round += 1
        mb = self.comm_stats["minibatch"]
        mb["rounds"] = self._round
        mb["last_batch"] = sorted(shards)
        mb["max_age"] = int(self._ages.max())

    def train(self, epochs: int, verbose: bool = False) -> "TrainLog":
        from repro.core.serial import TrainLog
        log = TrainLog()
        # host spans: ``train.step`` and ``train.eval`` dispatch the
        # programs, ``train.wait`` waits for the step (``epoch_time_s``),
        # ``train.sync`` reads the metrics back to the host
        for epoch in range(epochs):
            t0 = time.perf_counter()
            with spans.span("train.step"):
                self.step()
            with spans.span("train.wait"):
                jax.block_until_ready(self.state.zs[-1])
            dt = time.perf_counter() - t0
            with spans.span("train.eval"):
                tr, te, res = self._metrics(self.state)
                lag = self._lagrangian(self.state)
            with spans.span("train.sync"):
                tr, te, lag, res = float(tr), float(te), float(lag), \
                    float(res)
            log.epoch.append(epoch)
            log.train_acc.append(tr)
            log.test_acc.append(te)
            log.lagrangian.append(lag)
            log.residual.append(res)
            log.epoch_time_s.append(dt)
            if verbose:
                print(f"[parallel-admm] epoch {epoch:3d} train {tr:.3f} "
                      f"test {te:.3f} lagr {lag:.4f} res {res:.2e} "
                      f"({dt*1e3:.1f} ms)")
        return log
