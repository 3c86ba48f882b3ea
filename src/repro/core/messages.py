"""First/second-order community messages (paper Appendix A, eq. 4).

In the paper, community ``m`` needs, for its ``Z_{l,m}`` subproblem:

  p_{l,r→m}  = Ã_{m,r} Z_{l,r} W_{l+1}                    (first order)
  s_{l,r→m}  = [Z_{l+1,r},  Σ_{r'∈N_r∪{r}\\{m}} p_{l,r'→r}]  (second order)

and eq. (4) shows the second-order payload is assembled by community r from
its *received* first-order messages — no second-hop communication.

On a TPU mesh the agents are shards on the ``comm`` axis.  The quantity each
community relays is its full first-order aggregate

  q_{l,r} = Σ_{r'∈N_r∪{r}} p_{l,r'→r} = (Σ_{r'} Ã_{r,r'} Z_{l,r'}) W_{l+1}

from which the receiver reconstructs the paper's s-message by subtracting its
own contribution:  s²_{l,r→m} = q_{l,r} − Ã_{r,m} Z_{l,m} W_{l+1}  (using
Ã_{r,m} = Ã_{m,r}ᵀ, Ã symmetric).  This file provides those helpers; the
shard_map trainer in ``parallel.py`` uses them, and tests assert equality
with the paper's literal per-neighbour message formulas.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def row_aggregate(a_row: Array, z_all: Array,
                  mask: Array | None = None) -> Array:
    """Σ_{r∈N_m} Ã_{m,r} Z_r — community m's first-order aggregation.

    a_row: (M, n_pad, n_pad) — m's row of Ã blocks (Ã_{m,r} for all r)
    z_all: (M, n_pad, C)     — all communities' Z (gathered)
    mask:  optional (M,) neighbour row; absent blocks contribute nothing
           (the blocks are zero anyway — the mask makes the paper's
           r ∈ N_m ∪ {m} restriction explicit and lets sparse backends skip)
    returns (n_pad, C)
    """
    if mask is not None:
        a_row = a_row * mask[:, None, None].astype(a_row.dtype)
    return jnp.einsum("rip,rpc->ic", a_row, z_all)


def first_order_messages(a_row: Array, z_all: Array, w_next: Array,
                         mask: Array | None = None) -> Array:
    """Stacked p_{l,r→m} for all r: (M, n_pad, C_next).  p[r] = Ã_{m,r} Z_r W."""
    if mask is not None:
        a_row = a_row * mask[:, None, None].astype(a_row.dtype)
    return jnp.einsum("rip,rpc->ric", a_row, z_all) @ w_next


def relay_aggregate(a_row: Array, z_all: Array, w_next: Array,
                    mask: Array | None = None) -> Array:
    """q_{l,m} = (Σ_r Ã_{m,r} Z_r) W_{l+1} — the payload community m relays."""
    return row_aggregate(a_row, z_all, mask) @ w_next


def gather_bytes(neighbor_mask: np.ndarray, n_pad: int,
                 feature_dims: Sequence[int], itemsize: int = 4) -> dict:
    """Collective bytes per ADMM iteration: full all-gather vs the
    neighbour-only volume the paper's topology actually needs.

    Every iteration gathers one (M, n_pad, C) payload per entry of
    ``feature_dims`` (the Z_l layers, U, and the relay aggregates q).  The
    full all-gather moves M payload rows to every agent; neighbour-aware
    exchange moves only the rows r ∈ N_m ∪ {m}, i.e. nnz(neighbor_mask)
    row-payloads in total instead of M².
    """
    nbr = np.asarray(neighbor_mask)
    m = nbr.shape[0]
    nnz = int(nbr.sum())
    per_c = n_pad * itemsize
    full = sum(m * m * c * per_c for c in feature_dims)
    needed = sum(nnz * c * per_c for c in feature_dims)
    return {"full_bytes": full, "needed_bytes": needed,
            "nnz_blocks": nnz, "dense_blocks": m * m,
            "savings_ratio": 1.0 - (needed / full if full else 0.0)}


def adjacency_bytes(neighbor_mask: np.ndarray, n_pad: int,
                    itemsize: int = 4) -> dict:
    """Device-resident adjacency bytes per representation.

    ``dense_bytes`` is the replicated-layout block tensor the parallel
    trainer shards row-wise in dense mode (M² blocks in total across the
    mesh); ``ell_bytes`` is the block-compressed (ELL) payload the
    compressed trainer holds instead — M·max_deg blocks plus the int32
    index / float32 mask planes; ``csr_bytes`` is the tighter
    CSR-of-blocks bound (nnz blocks, host-side).  ``itemsize`` is the ELL
    *block-store* element size (2 with ``adjacency_bf16``) — it scales
    only ``ell_bytes``; the dense and CSR baselines are always the f32
    tensors those representations actually are, so ``ell_ratio`` shows
    the bf16 win instead of silently halving the comparison point.  On
    power-law community graphs max_deg is ~constant in M, so ell_bytes
    grows ~linearly while dense_bytes grows quadratically.
    """
    nbr = np.asarray(neighbor_mask)
    m = nbr.shape[0]
    deg = nbr.sum(axis=1)
    max_deg = int(deg.max()) if m else 0
    nnz = int(nbr.sum())
    block = n_pad * n_pad
    dense = m * m * block * 4
    ell = m * max_deg * (block * itemsize + 4 + 4)
    return {
        "dense_bytes": dense,
        "ell_bytes": ell,
        "csr_bytes": nnz * block * 4,
        "nnz_blocks": nnz,
        "max_deg": max_deg,
        "block_itemsize": itemsize,
        "ell_ratio": ell / dense if m else 0.0,
    }


def pad_stats(neighbor_mask: np.ndarray, sizes: np.ndarray,
              row_counts: np.ndarray, n_pad: int,
              feature_dims: Sequence[int], itemsize: int = 4) -> dict:
    """Residual-padding accounting of a (possibly ragged) layout.

    ``sizes`` are the true community row counts, ``row_counts`` the padded
    counts actually processed (None = the global ``n_pad`` everywhere).
    Per ADMM iteration (one payload per entry of ``feature_dims``, the same
    convention as ``gather_bytes``):

      * ``pad_rows`` / ``pad_bytes`` — payload rows (bytes) that carry
        padding, Σ_m (row_counts[m] − sizes[m]);
      * ``pad_flops`` — MXU work the block aggregation spends on pad
        rows/cols: Σ_{(m,r)∈nbr} 2·C·(rc_m·rc_r − s_m·s_r), i.e. processed
        minus irreducible true-row FLOPs (the ELL kernel's row-count guards
        skip pad work at tile granularity; this is the row-exact bound).

    Bucketed row_counts shrink both against the global-pad baseline on any
    size-skewed partition — the drop CI guards via BENCH_speedup.json's
    ``m32_ragged`` section.
    """
    nbr = np.asarray(neighbor_mask, bool)
    s = np.asarray(sizes, dtype=np.int64)
    rc = np.full(s.shape, n_pad, dtype=np.int64) if row_counts is None \
        else np.asarray(row_counts, dtype=np.int64)
    if (rc < s).any():
        raise ValueError("row_counts below true community sizes")
    total_c = int(np.sum(list(feature_dims)))
    pad_rows = int((rc - s).sum())
    processed = float(np.outer(rc, rc)[nbr].sum())
    true = float(np.outer(s, s)[nbr].sum())
    agg_flops = 2.0 * total_c * processed
    pad_flops = 2.0 * total_c * (processed - true)
    return {
        "pad_rows": pad_rows,
        "pad_bytes": pad_rows * total_c * itemsize,
        "pad_flops": pad_flops,
        "agg_flops": agg_flops,
        "pad_flop_frac": pad_flops / agg_flops if agg_flops else 0.0,
        "padded_rows_total": int(rc.sum()),
        "true_rows_total": int(s.sum()),
    }


# ---------------------------------------------------------------------------
# neighbour-only point-to-point transport (ppermute round schedule)
# ---------------------------------------------------------------------------

# the ``jax.named_scope`` the exchanges' ppermute rounds run under, so a
# device trace attributes their ops (the send gathers, the
# collective-permutes and the receive scatters) to the exchange
EXCHANGE_SCOPE = "admm_exchange"

@dataclasses.dataclass(frozen=True)
class ExchangeRound:
    """One ``lax.ppermute`` round of the neighbour exchange.

    All shards run the round SPMD with the same ``(rows_pad, C)`` buffer
    shape; only the ``pairs`` actually transmit.  Rows are *node* rows: a
    community contributes only its true ``sizes[r]`` rows (row-exact), or
    all ``n_pad`` rows when the plan was built without sizes (the
    global-pad / whole-block behaviour).  ``send_idx[s]`` lists the flat
    local node-row indices (into the (k·n_pad, C)-flattened local payload)
    shard s packs, 0-padded past its true row count; ``recv_slot[s]`` the
    flat receive-buffer rows (into (r_pad·n_pad, C)) the arriving rows
    scatter into, with pad positions pointing one past the buffer end so a
    ``mode='drop'`` scatter discards them.  For each pair both tables are
    written from the same ordered row list, so row t on the source lines up
    with row t on the destination.
    """
    offset: int                      # colour id of the round (edge colouring)
    pairs: tuple[tuple[int, int], ...]
    rows_pad: int                    # padded node rows per participating shard
    send_idx: np.ndarray             # (n_shards, rows_pad) int32 flat rows
    recv_slot: np.ndarray            # (n_shards, rows_pad) int32; OOB=drop
    true_rows: int                   # Σ real node rows over pairs (no padding)
    # packed-plane twins (plans built with row_counts): rows into the local
    # (plane_rows, C) state plane / the (recv_plane_rows, C) receive plane
    send_rows_packed: "np.ndarray | None" = None
    recv_rows_packed: "np.ndarray | None" = None


@dataclasses.dataclass(frozen=True)
class NeighborExchange:
    """Static neighbour-only exchange plan over the community topology.

    Built host-side from ``neighbor_mask`` (equivalently the per-shard
    union of ``BlockCSR.ell_indices``): shard s must end up holding the
    payload rows of ``needed_ids[s]`` — its own k lanes (resident, no
    wire) plus every neighbour community of any of its lanes.  Messages
    (src shard → dst shard, list of community ids) are coloured into
    ``ppermute`` rounds by ring offset (sharding.partition.
    ring_round_coloring), so one exchange is ``len(rounds)`` static
    collective-permutes moving ``(rows_pad, C)`` node-row buffers — no
    ``(M, n_pad, C)`` gathered tensor is ever materialised.  Receive
    buffers are lane-major: ``(r_pad, n_pad, C)`` with each shard's own
    lanes and neighbour rows at the slots ``localize_indices`` remaps the
    ELL indices onto.

    Row-exact mode (``sizes`` given, ``row_exact=True``): each wired
    community contributes only its true node rows, so on a size-skewed
    partition the wire volume tracks Σ sizes over cross-shard messages
    instead of (#messages)·n_pad — the pad rows never leave the device.
    Receive-buffer rows past a community's size simply stay zero, exactly
    the value the whole-block transport would have delivered.
    """
    n_shards: int
    lanes_per_shard: int
    n_pad: int
    r_pad: int                       # receive-buffer rows (max over shards)
    needed_ids: tuple[tuple[int, ...], ...]   # per shard, slot -> global id
    own_slots: np.ndarray            # (n_shards, k) int32
    rounds: tuple[ExchangeRound, ...]
    sizes: tuple[int, ...] = ()      # per community wired rows (n_pad if not
    row_exact: bool = False          # row-exact)
    # packed-plane metadata (plans built with row_counts): the send side is
    # the shard's (plane_rows, C) state plane (PackedDeviceLayout); the
    # receive side a (recv_plane_rows, C) plane with slot j's community at
    # recv_offsets[s, j] for row_counts[gid] bucket rows
    row_counts: tuple[int, ...] = ()
    plane_rows: int = 0
    recv_plane_rows: int = 0
    local_offsets: "np.ndarray | None" = None   # (M,) row in the home plane
    recv_offsets: "np.ndarray | None" = None    # (n_shards, r_pad); OOB=unused
    own_copy_rows: "np.ndarray | None" = None   # (n_shards, recv_plane_rows)
    recv_unpack_rows: "np.ndarray | None" = None  # (n_shards, r_pad·n_pad)

    @property
    def num_rounds(self) -> int:
        return len(self.rounds)

    @property
    def packed(self) -> bool:
        """True when the plan carries packed-plane routing tables."""
        return self.recv_offsets is not None

    def slot_of(self, shard: int) -> dict[int, int]:
        """global community id -> receive-buffer slot on ``shard``."""
        return {int(r): i for i, r in enumerate(self.needed_ids[shard])}

    def localize_indices(self, ell_indices: np.ndarray,
                         ell_mask: np.ndarray) -> np.ndarray:
        """Remap global ELL neighbour ids to receive-buffer slots.

        ``ell_indices``: (M, max_deg) global community ids (community-major
        rows, as BlockCSR stores them).  Row m belongs to shard m // k;
        every masked-in id is in that shard's needed set by construction.
        Masked-out (padding) entries map to slot 0 — they are multiplied by
        the zero mask by every consumer, any in-range slot is fine.
        """
        idx = np.asarray(ell_indices)
        msk = np.asarray(ell_mask) > 0
        k = self.lanes_per_shard
        slot_tables = [self.slot_of(s) for s in range(self.n_shards)]
        out = np.zeros_like(idx, dtype=np.int32)
        for m in range(idx.shape[0]):
            slots = slot_tables[m // k]
            for d in np.flatnonzero(msk[m]):
                out[m, d] = slots[int(idx[m, d])]
        return out

    def localized_offsets(self, ell_indices: np.ndarray,
                          ell_mask: np.ndarray) -> np.ndarray:
        """Receive-plane *row offsets* of every ELL neighbour slot.

        The packed twin of ``localize_indices``: instead of a buffer slot
        (a multiple-of-``n_pad`` stride), each masked-in (m, d) entry maps
        to the first receive-plane row of its neighbour's bucket —
        ``recv_offsets[shard(m), slot]`` — which is what the offset-indexed
        ELL kernel scalar-prefetches to steer its Z DMA.  Masked-out
        entries map to row 0 (in range, multiplied away by the mask).
        """
        if self.recv_offsets is None:
            raise ValueError("plan built without row_counts has no packed "
                             "receive plane — pass row_counts to "
                             "build_neighbor_exchange")
        loc = self.localize_indices(ell_indices, ell_mask)
        msk = np.asarray(ell_mask) > 0
        k = self.lanes_per_shard
        out = np.zeros_like(loc, dtype=np.int32)
        for m in range(loc.shape[0]):
            offs = self.recv_offsets[m // k]
            for d in np.flatnonzero(msk[m]):
                out[m, d] = offs[loc[m, d]]
        return out


def plane_read_offsets(ell_indices: np.ndarray, ell_mask: np.ndarray,
                       local_offsets: np.ndarray) -> np.ndarray:
    """Resident-plane row offsets of every ELL neighbour slot.

    The single-plane twin of ``NeighborExchange.localized_offsets``: when
    every community is resident on one packed plane (serving, or a 1-shard
    mesh) there is no receive buffer to remap through — each masked-in
    (m, d) slot reads its neighbour's bucket starting at
    ``local_offsets[ell_indices[m, d]]``.  Masked-out slots map to row 0
    (in range; multiplied away by the mask).  This is the halo-read table
    the serving engine scalar-prefetches into the packed ELL kernel.
    """
    idx = np.asarray(ell_indices)
    msk = np.asarray(ell_mask) > 0
    offs = np.asarray(local_offsets, dtype=np.int32)
    return np.where(msk, offs[idx], 0).astype(np.int32)


def self_slot_mask(ell_indices: np.ndarray, ell_mask: np.ndarray
                   ) -> np.ndarray:
    """(M, max_deg) float32 marking each ELL row's *self* (diagonal) slot.

    ``ell_mask - self_slot_mask`` is then the cross-community (halo) mask:
    the serving engine aggregates the two halves separately so the halo
    part — the only part that depends on other communities — can be cached
    and invalidated on its own (kernels.ops.community_halo_spmm).
    """
    idx = np.asarray(ell_indices)
    msk = np.asarray(ell_mask) > 0
    rows = np.arange(idx.shape[0])[:, None]
    return ((idx == rows) & msk).astype(np.float32)


def build_neighbor_exchange(neighbor_mask: np.ndarray, n_shards: int,
                            n_pad: int,
                            sizes: np.ndarray | None = None,
                            row_counts: np.ndarray | None = None
                            ) -> NeighborExchange:
    """Construct the static round schedule for a community topology.

    ``sizes`` (optional, (M,) true rows per community) switches the plan to
    row-exact packing: each cross-shard message carries only the true node
    rows of its communities.  Without it every community wires all
    ``n_pad`` rows — byte-identical to the historic whole-block schedule.

    ``row_counts`` (optional, (M,) bucket rows per community,
    ``CommunityLayout.eff_row_counts``) additionally equips the plan with
    *packed-plane* routing tables: send rows index the shard's packed
    Σ-bucket-rows state plane (``PackedDeviceLayout``) and receive rows a
    packed receive plane with one bucket per needed slot, so a packed
    trainer never materialises a strided ``(r_pad, n_pad, C)`` buffer on
    the wire path.  The wired rows themselves are unchanged — packed and
    strided plans schedule byte-identical rounds.
    """
    from repro.core.graph import shard_neighbor_graph
    from repro.sharding.partition import ring_round_coloring

    nbr = np.asarray(neighbor_mask, bool)
    m = nbr.shape[0]
    needed, _ = shard_neighbor_graph(nbr, n_shards)
    k = m // n_shards
    row_exact = sizes is not None
    wired = np.full(m, n_pad, dtype=np.int64) if sizes is None \
        else np.asarray(sizes, dtype=np.int64)
    if wired.shape != (m,) or (wired < 0).any() or (wired > n_pad).any():
        raise ValueError(f"sizes must be (M,) in [0, n_pad={n_pad}]")
    r_pad = max(len(ids) for ids in needed)
    slot_of = [{int(r): i for i, r in enumerate(ids)} for ids in needed]

    packed = row_counts is not None
    if packed:
        rc = np.asarray(row_counts, dtype=np.int64)
        if rc.shape != (m,) or (rc > n_pad).any() or (rc < wired).any():
            raise ValueError("row_counts must be (M,) in [wired rows, "
                             f"n_pad={n_pad}] — buckets cover what is wired")
        local_offsets = np.zeros(m, dtype=np.int32)
        for s in range(n_shards):
            local_offsets[s * k:(s + 1) * k] = np.concatenate(
                [[0], np.cumsum(rc[s * k:(s + 1) * k])[:-1]])
        plane_rows = max(int(rc.reshape(n_shards, k).sum(axis=1).max()), 8)
        recv_offsets = np.full((n_shards, r_pad), 0, dtype=np.int32)
        recv_rows = np.zeros(n_shards, dtype=np.int64)
        for s in range(n_shards):
            cnts = [int(rc[g]) for g in needed[s]]
            offs = np.concatenate([[0], np.cumsum(cnts)]).astype(np.int32)
            recv_offsets[s, :len(cnts)] = offs[:-1]
            recv_rows[s] = offs[-1]
        recv_plane_rows = max(int(recv_rows.max()), 8)
        # unused trailing slots point one past the plane (drop/fill)
        for s in range(n_shards):
            recv_offsets[s, len(needed[s]):] = recv_plane_rows
        own_copy_rows = np.full((n_shards, recv_plane_rows), plane_rows,
                                dtype=np.int32)
        recv_unpack = np.full((n_shards, r_pad * n_pad), recv_plane_rows,
                              dtype=np.int32)
        for s in range(n_shards):
            for slot, gid in enumerate(needed[s]):
                cnt = int(rc[gid])
                rows = np.arange(cnt)
                recv_unpack[s, slot * n_pad: slot * n_pad + cnt] = \
                    recv_offsets[s, slot] + rows
                if gid // k == s:           # resident lane: local plane copy
                    own_copy_rows[s, recv_offsets[s, slot]:
                                  recv_offsets[s, slot] + cnt] = \
                        local_offsets[gid] + rows
    else:
        rc = None
        local_offsets = recv_offsets = own_copy_rows = recv_unpack = None
        plane_rows = recv_plane_rows = 0

    own_slots = np.zeros((n_shards, k), dtype=np.int32)
    for s in range(n_shards):
        for i in range(k):
            own_slots[s, i] = slot_of[s][s * k + i]

    # messages grouped by ring offset; ids kept sorted per (src, dst) pair
    msgs: dict[tuple[int, int], list[int]] = {}
    for dst in range(n_shards):
        for r in needed[dst]:
            src = int(r) // k
            if src != dst:
                msgs.setdefault((src, dst), []).append(int(r))
    colored = ring_round_coloring(msgs.keys(), n_shards)

    def msg_rows(pair):                 # true node rows of one message
        return int(sum(wired[r] for r in msgs[pair]))

    rounds = []
    for offset, pairs in colored.items():
        # Row-exact plans may split a colour round into power-of-two
        # size-bucketed sub-rounds: every round's buffer pads to its
        # largest message, so letting a 10-row and a 500-row message share
        # a round would wire 490 pad rows — grouping pairs whose row
        # counts share a bucket bounds round padding by the bucket ratio
        # (< 2×) instead of the offset's largest message.  Each sub-round
        # is a subset of a partial permutation, hence still one.  The
        # split is taken only when it at least halves the round's
        # scheduled wire: each extra round is an extra collective launch
        # whose SPMD buffer every shard materialises, so on near-uniform
        # message sizes (where padding is small anyway) one round per
        # offset stays cheaper end-to-end.  Whole-block plans always keep
        # one round per offset (all messages are count·n_pad rows — the
        # historic schedule, byte-identical).
        grouped = [list(pairs)]
        if row_exact:
            groups: dict[int, list] = {}
            for p in pairs:
                rows = msg_rows(p)
                bucket = 1 << max(0, int(np.ceil(np.log2(max(1, rows)))))
                groups.setdefault(bucket, []).append(p)
            split = [grp for _, grp in sorted(groups.items())]
            plain_wire = len(pairs) * max(msg_rows(p) for p in pairs)
            split_wire = sum(len(g) * max(msg_rows(p) for p in g)
                             for g in split)
            if 2 * split_wire <= plain_wire:
                grouped = split
        for grp in grouped:
            rows_pad = max(msg_rows(p) for p in grp)
            if rows_pad == 0:
                continue                # all-empty messages: nothing to wire
            send_idx = np.zeros((n_shards, rows_pad), dtype=np.int32)
            recv_slot = np.full((n_shards, rows_pad), r_pad * n_pad,
                                dtype=np.int32)
            send_pk = np.zeros((n_shards, rows_pad), dtype=np.int32) \
                if packed else None
            recv_pk = np.full((n_shards, rows_pad), recv_plane_rows,
                              dtype=np.int32) if packed else None
            for src, dst in grp:
                t = 0
                for r in msgs[(src, dst)]:
                    rows = int(wired[r])
                    send_idx[src, t:t + rows] = \
                        (r - src * k) * n_pad + np.arange(rows)
                    recv_slot[dst, t:t + rows] = \
                        slot_of[dst][r] * n_pad + np.arange(rows)
                    if packed:
                        send_pk[src, t:t + rows] = \
                            local_offsets[r] + np.arange(rows)
                        recv_pk[dst, t:t + rows] = \
                            recv_offsets[dst, slot_of[dst][r]] \
                            + np.arange(rows)
                    t += rows
            rounds.append(ExchangeRound(
                offset=offset, pairs=tuple(grp), rows_pad=rows_pad,
                send_idx=send_idx, recv_slot=recv_slot,
                true_rows=sum(msg_rows(p) for p in grp),
                send_rows_packed=send_pk, recv_rows_packed=recv_pk))

    return NeighborExchange(
        n_shards=n_shards, lanes_per_shard=k, n_pad=n_pad, r_pad=r_pad,
        needed_ids=tuple(tuple(int(r) for r in ids) for ids in needed),
        own_slots=own_slots, rounds=tuple(rounds),
        sizes=tuple(int(v) for v in wired), row_exact=row_exact,
        row_counts=tuple(int(v) for v in rc) if packed else (),
        plane_rows=plane_rows, recv_plane_rows=recv_plane_rows,
        local_offsets=local_offsets, recv_offsets=recv_offsets,
        own_copy_rows=own_copy_rows, recv_unpack_rows=recv_unpack)


def restrict_exchange(plan: NeighborExchange,
                      sampled_shards) -> NeighborExchange:
    """Sampled-round sub-schedule: the plan restricted to the pairs a
    community minibatch actually reads.

    Under stochastic community minibatching only the *sampled* shards'
    subproblems run, so only they need to receive — a ppermute pair
    ``(src, dst)`` survives iff ``dst`` is sampled.  The source side is
    NOT filtered: an unsampled neighbour's (stale, exact) Z/U rows still
    feed every sampled consumer's coupling terms, so unsampled shards
    keep sending.  Unsampled edges — pairs into unsampled shards — carry
    zero wire: their rounds either shrink or vanish.

    Buffer geometry is untouched (``needed_ids``/slots/``r_pad``/packed
    plane tables), so ELL indices and offsets localized against the full
    plan stay valid on the sub-schedule; rows a dropped pair would have
    delivered simply stay zero, values an unsampled consumer never
    reads.  Kept rounds re-pad to their largest surviving message and
    all-dropped rounds disappear, so ``exchange_bytes`` on the sub-plan
    prices exactly the sampled wire.  Restricting to the full shard set
    returns ``plan`` itself — the compiled full-batch program is the
    batch_fraction=1.0 program, bit for bit.
    """
    sampled = frozenset(int(s) for s in sampled_shards)
    if not sampled:
        raise ValueError("sampled_shards must be non-empty")
    if not sampled <= set(range(plan.n_shards)):
        raise ValueError(f"sampled shards {sorted(sampled)} out of range "
                         f"for n_shards={plan.n_shards}")
    if len(sampled) == plan.n_shards:
        return plan
    limit = plan.r_pad * plan.n_pad
    rounds = []
    for rnd in plan.rounds:
        kept = tuple(p for p in rnd.pairs if p[1] in sampled)
        if not kept:
            continue
        # per-pair true rows: a round is a partial permutation, so each
        # destination receives exactly one message — its in-range
        # recv_slot entries count that message's rows
        rows_of = {p: int((rnd.recv_slot[p[1]] < limit).sum())
                   for p in kept}
        rows_pad = max(rows_of.values())
        if rows_pad == 0:
            continue
        rounds.append(ExchangeRound(
            offset=rnd.offset, pairs=kept, rows_pad=rows_pad,
            send_idx=rnd.send_idx[:, :rows_pad],
            recv_slot=rnd.recv_slot[:, :rows_pad],
            true_rows=sum(rows_of.values()),
            send_rows_packed=None if rnd.send_rows_packed is None
            else rnd.send_rows_packed[:, :rows_pad],
            recv_rows_packed=None if rnd.recv_rows_packed is None
            else rnd.recv_rows_packed[:, :rows_pad]))
    return dataclasses.replace(plan, rounds=tuple(rounds))


def bf16_wire(collective: Callable[[Array], Array],
              payload: Array) -> Array:
    """Run ``collective`` on a bf16-compressed payload (half the wire
    bytes) and restore the operand dtype.  The bf16 value travels bitcast
    as uint16 — a plain convert would be hoisted back to f32 by XLA's
    convert-mover, silently undoing the compression (§Perf log).  Each p2p
    round's payload goes through this wrapper.
    """
    dt = payload.dtype
    if dt != jnp.float32:
        return collective(payload)
    wire = jax.lax.bitcast_convert_type(
        payload.astype(jnp.bfloat16), jnp.uint16)
    wire = collective(wire)
    return jax.lax.bitcast_convert_type(wire, jnp.bfloat16).astype(dt)


def exchange_neighbors_packed(plan: NeighborExchange, x_plane: Array,
                              axis: str, comm_bf16: bool = False,
                              staged: bool = False):
    """Run the plan on the packed state plane inside ``shard_map``.

    ``x_plane``: (plane_rows, C) — this shard's packed Σ-bucket-rows
    state (``PackedDeviceLayout``).  Returns the packed receive plane
    ``(recv_plane_rows, C)``: slot j's bucket rows live at
    ``recv_offsets[s, j]``, own lanes copied locally, neighbour rows
    arriving through the plan's ppermute rounds, each round moving the
    true rows of its messages.

    With ``staged=True`` the *incremental* buffer states are returned as
    a list — ``[after own-copy, after round 0, ..., final]`` — so a
    consumer can start aggregating the slots a round has already
    delivered while later rounds are still on the wire (the
    double-buffered overlap schedule; see ``arrival_rounds``).
    """
    if plan.recv_offsets is None:
        raise ValueError("plan built without row_counts cannot route the "
                         "packed plane")
    if plan.n_shards == 1:
        # one shard hosts every community and the needed-ids slot order is
        # the lane order, so the receive plane IS the local plane
        return [x_plane] if staged else x_plane
    sid = jax.lax.axis_index(axis)
    own_tbl = jnp.asarray(plan.own_copy_rows)[sid]
    buf = jnp.take(x_plane, own_tbl, axis=0, mode="fill", fill_value=0)
    bufs = [buf]
    with jax.named_scope(EXCHANGE_SCOPE):
        for rnd in plan.rounds:
            payload = x_plane[jnp.asarray(rnd.send_rows_packed)[sid]]
            permute = partial(jax.lax.ppermute, axis_name=axis,
                              perm=list(rnd.pairs))
            payload = bf16_wire(permute, payload) if comm_bf16 \
                else permute(payload)
            buf = buf.at[jnp.asarray(rnd.recv_rows_packed)[sid]].set(
                payload, mode="drop")
            bufs.append(buf)
    return bufs if staged else buf


def arrival_rounds(plan: NeighborExchange) -> np.ndarray:
    """(n_shards, r_pad) int32: index of the ppermute round that delivers
    each receive slot's payload; -1 for resident own lanes (available
    before any wire) and never-wired padding slots."""
    arr = np.full((plan.n_shards, plan.r_pad), -1, dtype=np.int32)
    limit = plan.r_pad * plan.n_pad
    for ri, rnd in enumerate(plan.rounds):
        for _, dst in rnd.pairs:
            rows = rnd.recv_slot[dst]
            slots = np.unique(rows[rows < limit] // plan.n_pad)
            arr[dst, slots] = ri
    return arr


def overlap_stats(plan: NeighborExchange, neighbor_mask: np.ndarray,
                  feature_dims: Sequence[int], itemsize: int = 4,
                  enabled: bool = False,
                  peak_flops: float | None = None,
                  ici_bw: float | None = None) -> dict:
    """Analytic exposed-vs-total wire time of the round schedule.

    Models the double-buffered overlap the staged exchange enables: while
    round r is on the wire, a shard can aggregate every ELL slot whose
    payload is already resident (own lanes before round 0, round r' < r
    arrivals after).  Per round, the exposed wire time is what the
    available aggregation work cannot hide:

        exposed_r = max(0, t_wire(r) − credit_r)

    with ``credit`` the pipelined budget of hideable compute (unspent
    credit carries forward; compute of slots arriving in the final round
    runs after the wire and hides nothing).  Wire time prices each
    round's per-pair payload over one ICI link; compute prices the
    row-exact block-aggregation FLOPs (2·rc_m·rc_src·ΣC per consumed ELL
    slot) at peak MXU throughput — both from ``repro.launch.mesh``, so
    the metric is a deterministic property of the schedule, not a
    wall-clock sample.  The worst shard's exposure is reported (SPMD
    rounds advance at the slowest participant).

    ``overlap_efficiency`` = hidden / total wire time ∈ [0, 1];
    ``exposed_wire_bytes`` = exposed seconds × link bandwidth is what the
    roofline prices instead of total wire bytes (``benchmarks/roofline``).
    """
    if peak_flops is None or ici_bw is None:
        from repro.launch.mesh import ICI_BW, PEAK_FLOPS
        peak_flops = PEAK_FLOPS if peak_flops is None else peak_flops
        ici_bw = ICI_BW if ici_bw is None else ici_bw
    nbr = np.asarray(neighbor_mask, bool)
    m = nbr.shape[0]
    k = plan.lanes_per_shard
    rc = np.asarray(plan.row_counts, dtype=np.int64) if plan.row_counts \
        else np.full(m, plan.n_pad, dtype=np.int64)
    total_c = int(np.sum(list(feature_dims)))
    n_gathers = len(list(feature_dims))
    arr = arrival_rounds(plan)
    t_wire = [r.rows_pad * total_c * itemsize / ici_bw for r in plan.rounds]
    total = float(sum(t_wire))

    # per-shard hideable compute per arrival group (seconds, all gathers)
    worst_exposed = 0.0
    for s in range(plan.n_shards):
        slot_gid = plan.needed_ids[s]
        group_flops = np.zeros(plan.num_rounds + 1)
        for lane in range(s * k, (s + 1) * k):
            for slot, gid in enumerate(slot_gid):
                if not nbr[lane, gid]:
                    continue
                g = int(arr[s, slot]) + 1          # own lanes -> group 0
                group_flops[g] += 2.0 * int(rc[lane]) * int(rc[gid]) \
                    * total_c
        credit = group_flops[0] / peak_flops
        exposed = 0.0
        for ri, tw in enumerate(t_wire):
            hidden = min(tw, credit)
            exposed += tw - hidden
            credit += group_flops[ri + 1] / peak_flops - hidden
        worst_exposed = max(worst_exposed, exposed)

    eff = 1.0 - worst_exposed / total if total > 0 else 0.0
    # scheduled bytes of the priced plan — every pair of every round moves
    # its rows_pad rows; this is exactly exchange_bytes(plan)["wire_bytes"]
    # (the per-second totals above price per *round* over one link, so
    # they are not byte-convertible when a round carries several pairs)
    wire_rows = sum(len(r.pairs) * r.rows_pad for r in plan.rounds)
    return {
        "enabled": bool(enabled),
        "num_rounds": plan.num_rounds,
        "num_groups": plan.num_rounds + 1,
        "total_wire_s": total,
        "exposed_wire_s": worst_exposed,
        "hidden_wire_s": total - worst_exposed,
        "overlap_efficiency": eff,
        "total_wire_bytes": int(wire_rows * total_c * itemsize),
        "exposed_wire_bytes": int(worst_exposed * ici_bw),
        "num_gathers": n_gathers,
        "model": {"peak_flops": peak_flops, "ici_bw": ici_bw,
                  "itemsize": itemsize},
    }


def exchange_bytes(plan: NeighborExchange, feature_dims: Sequence[int],
                   itemsize: int = 4) -> dict:
    """Scheduled wire volume of the p2p transport per ADMM iteration.

    ``wire_bytes`` is what the ``ppermute`` rounds actually move: per round,
    every participating pair transmits the round's padded ``rows_pad``
    *node* rows (shards outside the round's partial permutation move
    nothing).  A whole-block plan wires ``n_pad`` rows per community; a
    row-exact plan only the true sizes.  ``p2p_needed_bytes`` counts only
    the true (round-padding-free) rows, so ``wire_bytes ==
    p2p_needed_bytes + padding_bytes`` exactly — the invariant
    ``verify_transport_bytes`` enforces against the mask-derived
    ``gather_bytes`` accounting.
    """
    wire_rows = sum(len(r.pairs) * r.rows_pad for r in plan.rounds)
    true_rows = sum(r.true_rows for r in plan.rounds)
    wire = sum(wire_rows * c * itemsize for c in feature_dims)
    needed = sum(true_rows * c * itemsize for c in feature_dims)
    return {"wire_bytes": wire, "p2p_needed_bytes": needed,
            "padding_bytes": wire - needed, "wire_rows": wire_rows,
            "true_rows": true_rows, "num_rounds": plan.num_rounds,
            "r_pad": plan.r_pad, "row_exact": plan.row_exact,
            "lanes_per_shard": plan.lanes_per_shard}


def verify_transport_bytes(stats: dict) -> dict:
    """Invariant check tying the p2p schedule to the mask-derived stats.

    Hard invariants (raise — true by construction, a violation means the
    schedule or accounting is broken): (a) the transport never moves more
    than the all-gather it replaces, (b) wire == true scheduled rows +
    round padding, (c) the true rows stay within the block-level
    ``needed_bytes`` the masks record (per-shard deduplication only
    shrinks them).

    ``wire_bytes <= needed_bytes`` *including* padding additionally holds
    whenever each shard hosts one community (k=1: every round row is a
    real row, zero padding) *and* the plan is whole-block — the benchmark
    sweeps and CI guards (benchmarks/check_bench.py) run in that regime
    and assert it strictly.  Row-exact plans can carry round padding even
    at k=1 (messages of different true sizes share a round), so there —
    as on multi-lane shards — padding overshoot is recorded as
    ``wire_within_needed`` rather than raised; the schedule is still
    correct, still bounded by the all-gather volume, and its *true* rows
    are strictly fewer than the whole-block plan's.
    """
    wire = stats["wire_bytes"]
    if wire > stats["full_bytes"]:
        raise ValueError(
            f"p2p transport moves more than all-gather: wire={wire} > "
            f"full={stats['full_bytes']}")
    if wire != stats["p2p_needed_bytes"] + stats["padding_bytes"]:
        raise ValueError(
            f"wire accounting inconsistent: {wire} != "
            f"{stats['p2p_needed_bytes']} + {stats['padding_bytes']}")
    if stats["p2p_needed_bytes"] > stats["needed_bytes"]:
        raise ValueError(
            f"scheduled rows exceed the mask-derived needed volume: "
            f"{stats['p2p_needed_bytes']} > {stats['needed_bytes']}")
    stats["wire_within_needed"] = wire <= stats["needed_bytes"]
    if stats.get("lanes_per_shard") == 1 and not stats.get("row_exact") \
            and not stats["wire_within_needed"]:
        raise ValueError(
            f"k=1 whole-block schedule has padding ({wire} > "
            f"{stats['needed_bytes']}) — impossible by construction, "
            f"accounting is broken")
    return stats


def second_order_from_relay(q_all: Array, a_row: Array, z_local: Array,
                            w_next: Array) -> Array:
    """s²_{l,r→m} for all r, reconstructed receiver-side (eq. 4).

    q_all:   (M, n_pad, C_next) — gathered relay aggregates q_{l,r}
    a_row:   (M, n_pad, n_pad)  — Ã_{m,r}; Ã_{r,m} = Ã_{m,r}ᵀ
    z_local: (n_pad, C_l)       — Z_{l,m}
    returns  (M, n_pad, C_next)
    """
    own_contrib = jnp.einsum("rnp,nc->rpc", a_row, z_local @ w_next)
    return q_all - own_contrib


def neighbor_preactivations(q_all: Array, a_row: Array, z_var: Array,
                            z_ref: Array, w_next: Array) -> Array:
    """Pre-activations of *every* community's next layer as a function of
    this community's variable ``z_var`` (with all other communities frozen
    at their k-th iterates, already baked into ``q_all`` via ``z_ref``):

        pre[r] = q_{l,r} + Ã_{r,m} (z_var − z_ref) W_{l+1}
               = s²_{l,r→m} + Ã_{r,m} z_var W_{l+1}

    For r ∉ N_m the Ã block is zero, so pre[r] is constant in z_var (those
    terms drop out of the gradient — the paper's neighbour-only coupling).
    """
    delta = (z_var - z_ref) @ w_next
    return q_all + jnp.einsum("rnp,nc->rpc", a_row, delta)
