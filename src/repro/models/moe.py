"""Mixture-of-Experts FFN: shared + routed experts, top-k, capacity dispatch.

Dispatch is scatter-based (no (T, E, C) one-hot einsum): tokens are ranked
within their expert by a cumulative-count over the top-k assignment matrix,
dropped beyond capacity, and scattered into per-expert buffers (E, C, D).
Expert weights carry a leading E axis that shards over the ``model`` mesh
axis (expert parallelism); under pjit the scatter/gather lowers to the
all-to-all-style collectives the roofline's collective term measures.

Matches DeepSeekMoE (arXiv:2401.06066) / DeepSeek-V3 (arXiv:2412.19437)
structure: fine-grained experts + shared experts + aux load-balance loss.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.layers import Params, dense_init, dtype_of

Array = jax.Array


def init_moe(cfg: ModelConfig, key) -> Params:
    moe = cfg.moe
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, moe.d_ff_expert, moe.num_experts

    def stack_init(k, shape):
        return dense_init(k, shape, dt, scale=1.0 / jnp.sqrt(shape[-2]))

    p: Params = {
        "router": dense_init(ks[0], (d, e), jnp.float32),  # f32 router
        "w_gate": stack_init(ks[1], (e, d, f)),
        "w_up": stack_init(ks[2], (e, d, f)),
        "w_down": stack_init(ks[3], (e, f, d)),
    }
    if moe.num_shared_experts:
        p["shared"] = layers.init_mlp(
            cfg, ks[4], d, moe.num_shared_experts * f)
    return p


def _expert_ffn(cfg: ModelConfig, p: Params, xs: Array) -> Array:
    """xs: (E, C, D) -> (E, C, D), vectorized over the expert axis."""
    if cfg.mlp in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else \
            lambda v: jax.nn.gelu(v, approximate=True)
        h = act(jnp.einsum("ecd,edf->ecf", xs, p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", xs, p["w_up"])
    elif cfg.mlp == "relu2":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", xs, p["w_up"])))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xs, p["w_up"]),
                        approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def apply_moe(cfg: ModelConfig, p: Params, x: Array
              ) -> tuple[Array, Array]:
    """x: (B, S, D) -> (out, aux_loss).

    Dispatch path selection: when sharding hints are active with
    ``moe_a2a`` and the expert count divides the 'model' axis, the
    explicit expert-parallel all-to-all dispatch runs (apply_moe_a2a);
    otherwise the portable scatter-based path below."""
    from repro.sharding import hints
    mesh = hints.active_mesh()
    if (hints.moe_a2a_enabled() and mesh is not None
            and "model" in mesh.axis_names
            and cfg.moe.num_experts % mesh.shape["model"] == 0
            and cfg.moe.num_experts >= mesh.shape["model"]
            and not _inside_manual_region()):
        return apply_moe_a2a(cfg, p, x, mesh)
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = moe.num_experts, moe.top_k
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    flat_expert = expert_ids.reshape(t * k)

    # load-balance aux loss (Switch-style): E * Σ_e f_e · p̄_e
    counts = jnp.zeros((e,), jnp.float32).at[flat_expert].add(1.0)
    frac_tokens = counts / (t * k)
    frac_probs = probs.mean(0)
    aux = moe.router_aux_weight * e * jnp.vdot(frac_tokens, frac_probs)

    # capacity floor of min(T·k, 16) keeps tiny (decode-sized) batches
    # effectively drop-free — binomial overflow beyond 16 slots at T·k/E
    # expected load is negligible, and cached decode must reproduce the
    # full forward (tests/test_decode_consistency.py)
    capacity = max(int(t * k / e * moe.capacity_factor), min(t * k, 32))

    # rank each (token, slot) within its expert via a stable sort — O(T·k)
    # memory (no (T·k, E) one-hot buffer)
    sort_idx = jnp.argsort(flat_expert, stable=True)
    sorted_experts = flat_expert[sort_idx]
    idx = jnp.arange(t * k, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_experts[1:] != sorted_experts[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    rank_sorted = idx - group_start
    rank = jnp.zeros((t * k,), jnp.int32).at[sort_idx].set(rank_sorted)
    keep = rank < capacity

    # scatter tokens into (E, C, D) buffers via masked scatter-ADD: every
    # kept (token, slot) owns a unique rank < capacity, so add == set, and
    # dropped tokens contribute zero — no trash row, so the buffer shape
    # stays exactly (E·C, D) and can be pinned to the expert ('model') axis
    # from creation (the scatter then lowers as an all-to-all instead of a
    # replicated scatter + reshard; see EXPERIMENTS.md §Perf).
    from repro.sharding import hints
    slot = flat_expert * capacity + jnp.minimum(rank, capacity - 1)
    src = jnp.repeat(hints.hint_tokens(xf), k, axis=0)       # (T*k, D)
    src = src * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e * capacity, d), x.dtype)
    buf, _ = hints.hint_moe_buffers(buf, buf)
    buf = buf.at[slot].add(src)
    expert_in = buf.reshape(e, capacity, d)

    expert_in, _ = hints.hint_moe_buffers(expert_in, expert_in)
    expert_out = _expert_ffn(cfg, p, expert_in)              # (E, C, D)
    expert_out, _ = hints.hint_moe_buffers(expert_out, expert_out)

    # gather back and weight by (renormalized, drop-masked) gates
    flat_out = expert_out.reshape(e * capacity, d)
    gathered = flat_out[slot]                                # (T*k, D)
    gates = (gate_vals.reshape(t * k) * keep).astype(x.dtype)
    combined = (gathered * gates[:, None]).reshape(t, k, d).sum(1)

    if moe.num_shared_experts:
        combined = combined + layers.apply_mlp(cfg, p["shared"], xf)
    return combined.reshape(b, s, d), aux


def _inside_manual_region() -> bool:
    """True when tracing inside an enclosing shard_map (e.g. the deferred-
    reduction train step is manual over the data axes) — nesting another
    shard_map over the same mesh there is invalid, so the a2a path defers
    to the portable dispatch."""
    try:
        am = jax.sharding.get_abstract_mesh()
        from jax.sharding import AxisType
        return any(t == AxisType.Manual
                   for t in getattr(am, "axis_types", ()))
    except Exception:
        return False


# ---------------------------------------------------------------------------
# explicit expert-parallel all-to-all dispatch (§Perf pair-2 iteration 4)
# ---------------------------------------------------------------------------

def apply_moe_a2a(cfg: ModelConfig, p: Params, x: Array, mesh
                  ) -> tuple[Array, Array]:
    """GShard-style MoE: tokens are locally packed into per-expert slots,
    exchanged with ONE all-to-all over the 'model' (expert) axis, run
    through the local expert shard, and returned with the reverse
    all-to-all — the collective volume is the dispatch floor
    (tokens × top_k × D × 2 directions) instead of the replicated
    scatter + all-reduce XLA derives from the portable path.

    shard_map is manual over BOTH the data axes (tokens stay local to
    their shard — routing/sort/pack are per-shard) and 'model' (experts).
    A first attempt manual over 'model' only forced global-token semantics
    (XLA materialized global sorts + gathers) and REGRESSED 14× — see
    EXPERIMENTS.md §Perf pair 2 iteration 4.  Because the data axes are
    manual here, this path is enabled for prefill/decode (plain jit); the
    deferred-reduction train step is already manual over data at an outer
    level and keeps the portable path.
    """
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    nm = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(xf, router, w_gate, w_up, w_down, shared):
        # manual over data axes AND 'model': xf (T_loc, D) is this data
        # shard's tokens (replicated over 'model'); w_* the local expert
        # shard (E/nm, ...) replicated over data
        t = xf.shape[0]
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
        flat_expert = expert_ids.reshape(t * k)

        counts = jnp.zeros((e,), jnp.float32).at[flat_expert].add(1.0)
        aux = moe.router_aux_weight * e * jnp.vdot(
            counts / (t * k), probs.mean(0))
        # average the load-balance statistic across all token shards
        aux = jax.lax.pmean(aux, dp + ("model",)) if dp else \
            jax.lax.pmean(aux, "model")

        capacity = max(int(t * k / e * moe.capacity_factor),
                       min(t * k, 32))
        sort_idx = jnp.argsort(flat_expert, stable=True)
        sorted_experts = flat_expert[sort_idx]
        idx = jnp.arange(t * k, dtype=jnp.int32)
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool),
             sorted_experts[1:] != sorted_experts[:-1]])
        group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
        rank = jnp.zeros((t * k,), jnp.int32).at[sort_idx].set(
            idx - group_start)
        keep = rank < capacity

        slot = flat_expert * capacity + jnp.minimum(rank, capacity - 1)
        src = jnp.repeat(xf, k, axis=0) * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((e * capacity, d), xf.dtype).at[slot].add(src)
        buf = buf.reshape(e, capacity, d)

        # THE dispatch: experts split over 'model', capacities concatenated
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)          # (E/nm, C·nm, D)

        if cfg.mlp in ("swiglu", "geglu"):
            act = jax.nn.silu if cfg.mlp == "swiglu" else \
                lambda v: jax.nn.gelu(v, approximate=True)
            h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
                jnp.einsum("ecd,edf->ecf", buf, w_up)
        elif cfg.mlp == "relu2":
            h = jnp.square(jax.nn.relu(
                jnp.einsum("ecd,edf->ecf", buf, w_up)))
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, w_up),
                            approximate=True)
        out = jnp.einsum("ecf,efd->ecd", h, w_down)   # (E/nm, C·nm, D)

        # return trip + local combine
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)          # (E, C, D)
        flat_out = out.reshape(e * capacity, d)
        gathered = flat_out[slot]
        gates = (gate_vals.reshape(t * k) * keep).astype(xf.dtype)
        combined = (gathered * gates[:, None]).reshape(t, k, d).sum(1)
        if moe.num_shared_experts:
            combined = combined + layers.apply_mlp(cfg, shared, xf)
        return combined, aux

    xf = x.reshape(b * s, d)
    shared = p.get("shared", {"up": jnp.zeros((0,)),
                              "down": jnp.zeros((0,))})
    rep2 = P(None, None)
    # tokens split over the data axes AND 'model' — every device routes a
    # distinct token slice (replicating tokens over 'model' would dispatch
    # nm identical copies: 16x redundant expert compute + a2a volume,
    # measured as §Perf pair-2 iteration 5's first attempt)
    t_axes = dp + ("model",)
    n_split = _dp_size(mesh) * nm
    tok = P(t_axes if (b * s) % n_split == 0 else
            (dp if (b * s) % _dp_size(mesh) == 0 else None), None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok, rep2, P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  jax.tree.map(lambda _: rep2, shared)),
        out_specs=(tok, P()),
        check_vma=False, axis_names=frozenset(dp + ("model",)))(
        xf, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)
    return out.reshape(b, s, d), aux


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
