"""Bridge from a built ``ParallelADMMTrainer`` to an analysis run.

``trainer_expectations`` distils the trainer's *host-side* contract —
exchange-plan rounds, scheduled wire bytes, layout shape
facts, donation intent, kernel specs — into the expectations dict the
rule registry checks the *compiled program* against.  ``analyze_trainer``
lowers/compiles the step (or reuses a caller-supplied HLO dump), traces
the jaxpr, and runs the registry.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np

from repro.analysis.findings import Report, Waiver
from repro.analysis.registry import AnalysisContext, run_rules


def _kernel_entries(tr: Any, n_shards: int) -> list[dict]:
    """One ELL-kernel spec per shard, with that shard's scalar operands:
    on many shards the packed kernel's receive-plane offsets (and the
    fused kernel's, when fused), on one shard the blocked kernel's global
    community ids."""
    from repro.kernels.community_spmm import (ell_fused_spec,
                                              ell_packed_spec, ell_spec)

    data = tr.data
    m, max_deg, n_pad, _ = data.ell_blocks.shape
    k = m // n_shards
    block_bytes = data.ell_blocks.dtype.itemsize
    idx = np.asarray(data.ell_indices)
    msk = np.asarray(data.ell_mask)
    rows = np.asarray(data.row_counts)
    nbrs = np.asarray(data.nbr_counts)
    c = max(tr.cfg.layer_dims)
    if n_shards > 1:
        csr = tr.layout.compress()
        off = np.asarray(tr._plan.localized_offsets(csr.ell_indices,
                                                    csr.ell_mask))
        off8 = np.where(msk != 0, off // 8, 0).astype(np.int32)
    entries = []
    for s in range(n_shards):
        sl = slice(s * k, (s + 1) * k)
        if n_shards > 1:
            # the aggregation reads the receive *plane* through 8-row
            # offsets, not a strided (z_lanes, n_pad, C)
            spec = ell_packed_spec(
                k, max_deg, n_pad, c, tr._plan.recv_plane_rows,
                block_bytes=block_bytes, z_bytes=4)
            scalars = {"ell_offsets8": off8[sl], "ell_mask": msk[sl],
                       "row_counts": rows[sl], "nbr_counts": nbrs[sl]}
            if tr.config.fused:
                # the fused aggregation→GEMM pass shares the packed
                # scalars; widest feature pair bounds its VMEM footprint
                fspec = ell_fused_spec(
                    k, max_deg, n_pad, c, c, tr._plan.recv_plane_rows,
                    block_bytes=block_bytes, z_bytes=4)
                entries.append({"spec": fspec, "scalars": dict(scalars)})
        else:
            spec = ell_spec(k, max_deg, n_pad, c, m,
                            block_bytes=block_bytes, z_bytes=4)
            scalars = {"ell_indices": idx[sl], "ell_mask": msk[sl],
                       "row_counts": rows[sl], "nbr_counts": nbrs[sl]}
        entries.append({"spec": spec, "scalars": scalars})
    return entries


def trainer_expectations(tr: Any) -> dict[str, Any]:
    """Expectations dict for the built-in rules, from the trainer's
    host-side plan and layout (see ``AnalysisContext`` for the keys).
    The trainer has one design — p2p exchange, bucketed padding, ELL
    adjacency, packed state — so those are fixed facts here."""
    from repro.core.parallel import AXIS, gathered_widths

    n_shards = tr.mesh.shape[AXIS]
    m = tr.data.num_parts
    n_pad = tr.layout.n_pad
    # fused only acts on the packed wire, which one shard does not have
    cs = gathered_widths(tr.cfg.layer_dims,
                         fused=tr.config.fused and n_shards > 1)
    max_c = max(tr.cfg.layer_dims)
    exp: dict[str, Any] = {
        "m_total": m,
        "n_shards": n_shards,
        "lanes": m // n_shards,
        "n_pad": n_pad,
        "max_deg": int(tr.data.ell_mask.shape[1]),
        "num_gathers": len(cs),
        "expect_donated": (".zs", ".u"),
    }
    # the minibatch trainer's compiled step runs a *restricted* round
    # schedule (messages.restrict_exchange): expectations come from the
    # active sub-plan, so the permute-schedule rule proves the sampled
    # program touches no unsampled shard pair
    plan = tr._active_plan
    if n_shards > 1:
        # single-shard meshes exchange nothing; the wire contract is only
        # meaningful (and checkable) on >1 shards
        if plan is not tr._plan:
            from repro.core import messages
            wire = messages.exchange_bytes(
                plan, cs, itemsize=2 if tr.config.comm_bf16 else 4)
            exp["collective_budget_bytes"] = int(wire["wire_bytes"])
        else:
            exp["collective_budget_bytes"] = int(tr.comm_stats["wire_bytes"])
        exp["round_pairs"] = [tuple(r.pairs) for r in plan.rounds]
        # the only legitimate all-reduces are the W-update psums: weight
        # gradients and line-search scalars, possibly combined by XLA
        w_bytes = sum(int(np.prod(w.shape)) * w.dtype.itemsize
                      for w in tr.state.weights)
        exp["allreduce_max_bytes"] = 2 * w_bytes + 4096
        # the packed plane feeds the wire: no blocked row stack taller
        # than the r_pad receive view (the 1-shard program keeps the
        # blocked body on its own rows)
        exp["packed_rows_bound"] = int(tr._plan.r_pad)
    # fused aggregation→GEMM: only the W-update may hand an aggregated
    # block stack to a dot (its line search re-evaluates the GEMM under a
    # varying W) — one aggregate per layer past the first, whose Ã Z_0 is
    # device data from construction; every Z-update site must run the
    # fused/reassociated form.  Only meaningful when the packed plane
    # feeds the wire, i.e. on more than one shard.
    exp["fused"] = bool(n_shards > 1 and tr.config.fused)
    if exp["fused"]:
        exp["fused_max_agg_handoffs"] = int(tr.cfg.num_layers) - 1
    # largest legitimate resident buffers: the adjacency store, the full
    # Z/U state stack, and one gathered payload; anything 4x past their
    # max is a blow-up
    state_bytes = sum(int(np.prod(z.shape)) * z.dtype.itemsize
                      for z in tr.state.zs) + int(np.prod(tr.state.u.shape)
                                                  ) * tr.state.u.dtype.itemsize
    gather_stack = m * n_pad * max_c * 4
    exp["hbm_intermediate_budget"] = 4 * max(
        int(tr.data.adjacency_nbytes), state_bytes, gather_stack)
    exp["kernels"] = _kernel_entries(tr, n_shards)
    return exp


def _donation_map(lowered: Any) -> dict[str, bool]:
    """{tree path: donated} from ``lowered.args_info``."""
    import jax

    out: dict[str, bool] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(lowered.args_info)
    for path, info in flat:
        key = "".join(str(p) for p in path)
        out[key] = bool(getattr(info, "donated", False))
    return out


def analyze_trainer(tr: Any, *,
                    hlo_text: Optional[str] = None,
                    config: str = "",
                    rules: Optional[Sequence[str]] = None,
                    waivers: Sequence[Waiver] = (),
                    with_jaxpr: bool = True) -> Report:
    """Run the rule registry over a trainer's compiled step.

    Pass ``hlo_text`` to reuse an already-compiled dump (the p2p proof
    subprocess compiles once and both asserts and lints the same text);
    otherwise the step is lowered and compiled here.
    """
    import jax

    exp = trainer_expectations(tr)
    # minibatch steps take (state, nbr_decay); _analysis_args is the
    # trainer's own account of its compiled step's signature
    args = getattr(tr, "_analysis_args", None) or (tr.state,)
    lowered = tr._step.lower(*args)
    exp["args_donated"] = _donation_map(lowered)
    if hlo_text is None:
        hlo_text = lowered.compile().as_text()
    jaxpr = None
    if with_jaxpr:
        jaxpr = jax.make_jaxpr(tr._step)(*args)
    ctx = AnalysisContext(hlo_text=hlo_text, jaxpr=jaxpr,
                          expectations=exp,
                          config=config or "p2p")
    return run_rules(ctx, rules=rules, waivers=waivers)
