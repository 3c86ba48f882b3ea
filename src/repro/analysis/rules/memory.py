"""Memory rules: nothing dense-adjacency-shaped, nothing over budget,
donated hot-loop buffers, no host round-trips.

PR 2's win was replacing the (M, M, n_pad, n_pad) dense adjacency with
block-compressed storage; these rules keep any program from silently
re-materialising it (or any other HBM blow-up) in an intermediate.
"""
from __future__ import annotations

from typing import Iterable

from repro.analysis.findings import Finding, Severity
from repro.analysis.registry import AnalysisContext, rule
from repro.analysis.rules.precision import _sub_jaxprs


@rule("memory/no-dense-adjacency")
def no_dense_adjacency(ctx: AnalysisContext) -> Iterable[Finding]:
    """No intermediate shaped like a dense block-adjacency row stack:
    trailing dims (n_pad, n_pad) with more leading blocks than the ELL
    bound lanes x max_deg allows."""
    exp = ctx.expectations
    n_pad = exp.get("n_pad")
    if ctx.hlo_text is None or not n_pad:
        return
    if exp.get("dense_adjacency_allowed"):
        return
    lanes = exp.get("lanes", 1)
    m_total = exp.get("m_total", 1)
    max_deg = exp.get("max_deg", m_total)
    # inputs may legitimately hold the full-M ELL block store (the trainer
    # closes over it); anything *computed* is bound by one shard's ELL
    # working set
    input_blocks = max(int(m_total) * int(max_deg), 1)
    compute_blocks = max(int(lanes) * int(max_deg), 1)
    for comp, ins in ctx.instructions():
        dims = ins.result_dims
        if len(dims) < 3 or dims[-1] != n_pad or dims[-2] != n_pad:
            continue
        blocks = 1
        for d in dims[:-2]:
            blocks *= d
        allowed_blocks = input_blocks if ins.op in ("parameter", "constant") \
            else compute_blocks
        if blocks > allowed_blocks:
            yield Finding(
                "memory/no-dense-adjacency", Severity.ERROR,
                f"%{ins.name} ({ins.op}) materialises {blocks} "
                f"({n_pad}x{n_pad}) blocks — dense-adjacency shaped; the "
                f"ELL bound is lanes x max_deg = {allowed_blocks}",
                location=ins.name,
                details={"shape": list(dims), "blocks": blocks,
                         "allowed_blocks": allowed_blocks,
                         "computation": comp.name})


@rule("memory/packed-resident-state")
def packed_resident_state(ctx: AnalysisContext) -> Iterable[Finding]:
    """Under packed state (``ParallelADMMTrainer(packed=True)`` on a
    multi-shard p2p mesh) the per-shard program never materialises a
    blocked row stack taller than the receive buffer: any computed
    (rows, n_pad, C) intermediate with ``rows > r_pad`` is a strided
    (M, n_pad, C)-shaped payload sneaking back in — exactly what the
    packed plane exists to retire."""
    exp = ctx.expectations
    n_pad = exp.get("n_pad")
    if ctx.hlo_text is None or not n_pad:
        return
    if not exp.get("state_packed"):
        return
    bound = int(exp.get("packed_rows_bound", 0))
    if bound <= 0:
        return
    for comp, ins in ctx.instructions():
        dims = ins.result_dims
        # blocked row stacks only: (rows, n_pad, C) with a feature-like
        # trailing dim (C == n_pad would be an adjacency block, which
        # memory/no-dense-adjacency already bounds)
        if len(dims) != 3 or dims[-2] != n_pad or dims[-1] == n_pad:
            continue
        if ins.op in ("parameter", "constant"):
            continue
        if dims[0] > bound:
            yield Finding(
                "memory/packed-resident-state", Severity.ERROR,
                f"%{ins.name} ({ins.op}) materialises a ({dims[0]}, "
                f"{n_pad}, {dims[-1]}) blocked row stack — taller than "
                f"the r_pad={bound} receive view the packed layout "
                f"allows per shard",
                location=ins.name,
                details={"shape": list(dims), "rows": dims[0],
                         "packed_rows_bound": bound,
                         "computation": comp.name})


def fused_agg_handoffs(closed_jaxpr: "object", n_pad: int) -> list[dict]:
    """Aggregated block stacks handed to a GEMM, from a dataflow walk.

    An *aggregation* is any equation consuming an ELL-block-shaped
    operand (trailing dims (n_pad, n_pad), rank ≥ 4 — the einsum oracle's
    block store or a pallas_call's block operand) whose output is a 3-D
    ``(rows, n_pad, C ≠ n_pad)`` stack.  The taint follows the stack
    only through ``add``/casts (the overlap path sums per-group partials
    before consuming them) — NOT through arbitrary shape-preserving ops,
    or the fused sites' own outputs would leak taint down activation and
    cotangent chains into the lane solvers' dots.  A *handoff* is
    recorded when a tainted var feeds a ``dot_general`` — each distinct
    stack counted once, however many dots the autodiff machinery derives
    from it.  Importable directly (tests); the registry rule wraps it.
    """
    handoffs: list[dict] = []
    seen: set[int] = set()
    carriers = {"add", "convert_element_type", "copy"}

    def shp(v):
        return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())

    def walk(jaxpr, path: str) -> None:
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        tainted: dict[int, dict] = {}
        consumed: dict[int, dict] = {}
        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            loc = f"{path}eqns[{i}]:{name}"
            if name == "dot_general":
                for v in eqn.invars:
                    if id(v) in tainted and id(v) not in consumed:
                        consumed[id(v)] = dict(tainted[id(v)], dot=loc)
            has_blocks = any(
                len(s) >= 4 and s[-1] == n_pad and s[-2] == n_pad
                for s in (shp(v) for v in eqn.invars))
            for v in eqn.outvars:
                s = shp(v)
                if len(s) != 3 or s[-2] != n_pad or s[-1] == n_pad:
                    continue
                if has_blocks:
                    tainted[id(v)] = {"producer": loc, "shape": list(s)}
                elif name in carriers:
                    src = next((tainted[id(u)] for u in eqn.invars
                                if id(u) in tainted), None)
                    if src is not None:
                        tainted[id(v)] = src
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, loc + "/")
        handoffs.extend(consumed.values())

    walk(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), "")
    return handoffs


@rule("memory/fused-no-intermediate")
def fused_no_intermediate(ctx: AnalysisContext) -> Iterable[Finding]:
    """Under ``TrainerConfig(fused=True)`` the compiled step materialises
    no HBM-resident aggregated ``(rows, n_pad, C)`` stack feeding a GEMM
    beyond the W-update allowance (one per layer past the first, whose
    Ã Z_0 comes from construction — its line search legitimately re-reads
    the aggregate under a varying W).  Checked on
    the traced jaxpr, where the handoff survives on every dispatch
    target: the TPU program would show a pallas_call output into a dot,
    the CPU oracle an einsum output into a dot — the fused kernel keeps
    the aggregate in VMEM scratch and the fused oracle reassociates it
    away, so either way the count stays at the W-update floor."""
    exp = ctx.expectations
    n_pad = exp.get("n_pad")
    if ctx.jaxpr is None or not n_pad or not exp.get("fused"):
        return
    allowed = int(exp.get("fused_max_agg_handoffs", 0))
    found = fused_agg_handoffs(ctx.jaxpr, int(n_pad))
    if len(found) > allowed:
        yield Finding(
            "memory/fused-no-intermediate", Severity.ERROR,
            f"{len(found)} aggregated (rows, {n_pad}, C) stacks feed a "
            f"dot_general — the fused step allows {allowed} (the "
            f"W-update line-search aggregates); extra handoffs mean an "
            f"unfused aggregation→GEMM site materialises its aggregate",
            location=found[0].get("dot"),
            details={"handoffs": found[:16],
                     "allowed": allowed, "count": len(found)})


@rule("memory/hbm-intermediate-budget")
def hbm_intermediate_budget(ctx: AnalysisContext) -> Iterable[Finding]:
    """No single intermediate exceeds ``hbm_intermediate_budget`` bytes."""
    budget = ctx.expectations.get("hbm_intermediate_budget")
    if ctx.hlo_text is None or budget is None:
        return
    for comp, ins in ctx.instructions():
        nbytes = max(ins.result_bytes, ins.tuple_bytes)
        if nbytes > budget and ins.op not in ("tuple", "parameter"):
            yield Finding(
                "memory/hbm-intermediate-budget", Severity.ERROR,
                f"%{ins.name} ({ins.op}) holds {nbytes} B "
                f"> budget {int(budget)} B",
                location=ins.name,
                details={"bytes": nbytes, "budget": int(budget),
                         "shape": list(ins.result_dims),
                         "computation": comp.name})


@rule("memory/no-full-graph-tensors")
def no_full_graph_tensors(ctx: AnalysisContext) -> Iterable[Finding]:
    """Under ``full_graph_rows`` no instruction — parameters included —
    holds a tensor whose leading dim reaches the full-graph row count.
    The serving hit path touches one community block and one request-row
    vector; a full-plane (Σ-bucket-rows) or (N, ...) operand means the
    program secretly depends on the whole graph and its latency will
    scale with it."""
    bound = ctx.expectations.get("full_graph_rows")
    if ctx.hlo_text is None or not bound:
        return
    for comp, ins in ctx.instructions():
        dims = ins.result_dims
        if not dims or ins.op == "tuple":
            continue
        if dims[0] >= int(bound):
            yield Finding(
                "memory/no-full-graph-tensors", Severity.ERROR,
                f"%{ins.name} ({ins.op}) holds a {list(dims)} tensor — "
                f"leading dim >= the full-graph row bound {int(bound)}",
                location=ins.name,
                details={"shape": list(dims), "bound": int(bound),
                         "computation": comp.name})


@rule("memory/donated-inputs")
def donated_inputs(ctx: AnalysisContext) -> Iterable[Finding]:
    """The trainer-step jit donates its state (Z/U stacks rebind every
    step; un-donated they double peak HBM)."""
    donated = ctx.expectations.get("args_donated")
    want = ctx.expectations.get("expect_donated")
    if not donated or not want:
        return
    for needle in want:
        matching = {p: d for p, d in donated.items()
                    if needle.lower() in p.lower()}
        if not matching:
            yield Finding(
                "memory/donated-inputs", Severity.WARNING,
                f"no trainer-step argument matches '{needle}' — "
                f"donation expectation is stale",
                details={"expected": needle,
                         "args": sorted(donated)[:16]})
            continue
        undonated = sorted(p for p, d in matching.items() if not d)
        if undonated:
            yield Finding(
                "memory/donated-inputs", Severity.ERROR,
                f"{len(undonated)} '{needle}' buffer(s) not donated to the "
                f"step jit (first: {undonated[0]})",
                location=undonated[0],
                details={"expected": needle, "undonated": undonated[:16]})


_HOST_OPS = {"infeed", "outfeed", "send", "recv", "send-done", "recv-done"}
_HOST_TARGETS = ("callback", "host", "Infeed", "Outfeed")


@rule("memory/host-transfer")
def host_transfer(ctx: AnalysisContext) -> Iterable[Finding]:
    """The compiled step makes no host<->device round-trips (infeed/
    outfeed/send/recv or host-callback custom-calls in the hot loop)."""
    if ctx.hlo_text is None:
        return
    for comp, ins in ctx.instructions():
        hit = ins.op in _HOST_OPS
        if not hit and ins.op == "custom-call":
            hit = any(t in ins.attrs for t in _HOST_TARGETS)
        if hit:
            yield Finding(
                "memory/host-transfer", Severity.ERROR,
                f"%{ins.name} ({ins.op}) transfers to/from host inside "
                f"the compiled step",
                location=ins.name,
                details={"computation": comp.name,
                         "attrs": ins.attrs[:160]})
