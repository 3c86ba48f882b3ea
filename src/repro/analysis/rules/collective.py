"""Collective rules: the transport contract, statically.

PR 3's headline — p2p transport compiles to collective-permutes only, with
the permute schedule and payload bytes matching the host-side
``NeighborExchange`` plan — is re-proved here against any compiled HLO,
not just the one config a test happens to build.
"""
from __future__ import annotations

from typing import Iterable, Iterator

from repro.analysis import hlo as H
from repro.analysis.findings import Finding, Severity
from repro.analysis.registry import AnalysisContext, rule


def _collective_instrs(ctx: AnalysisContext,
                       base: str) -> Iterator[tuple[H.Computation, H.Instr]]:
    """All instrs whose base op (start/done folded) equals ``base``,
    skipping the -done halves so async pairs count once."""
    for comp, ins in ctx.instructions():
        if ins.op.endswith("-done"):
            continue
        if H.base_op(ins) == base:
            yield comp, ins


@rule("collective/no-allgather-under-p2p")
def no_allgather_under_p2p(ctx: AnalysisContext) -> Iterable[Finding]:
    """A trainer step (expectations carrying ``n_shards``) contains no
    all-gather: many shards exchange rows by p2p ppermute, and one shard
    exchanges nothing."""
    if ctx.hlo_text is None or "n_shards" not in ctx.expectations:
        return
    hits = list(_collective_instrs(ctx, "all-gather"))
    if hits:
        yield Finding(
            "collective/no-allgather-under-p2p", Severity.ERROR,
            f"{len(hits)} all-gather op(s) compiled in a trainer step "
            f"(first: %{hits[0][1].name} in {hits[0][0].name})",
            location=hits[0][1].name,
            details={"count": len(hits),
                     "instructions": [i.name for _, i in hits[:8]]})


_ALL_COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
                    "all-to-all", "reduce-scatter", "collective-broadcast")


@rule("collective/zero-collectives")
def zero_collectives(ctx: AnalysisContext) -> Iterable[Finding]:
    """Under ``expect_zero_collectives`` the program contains no
    collective of any kind — the serving hit/recompute paths are
    single-device programs over one resident plane, so any collective is
    a sharded-training construct leaking into the serving build."""
    if ctx.hlo_text is None or \
            not ctx.expectations.get("expect_zero_collectives"):
        return
    hits = [(comp, ins) for base in _ALL_COLLECTIVES
            for comp, ins in _collective_instrs(ctx, base)]
    if hits:
        yield Finding(
            "collective/zero-collectives", Severity.ERROR,
            f"{len(hits)} collective op(s) in a program expected to be "
            f"collective-free (first: %{hits[0][1].name} "
            f"[{hits[0][1].op}] in {hits[0][0].name})",
            location=hits[0][1].name,
            details={"count": len(hits),
                     "instructions": [i.op for _, i in hits[:8]]})


@rule("collective/allreduce-payload")
def allreduce_payload(ctx: AnalysisContext) -> Iterable[Finding]:
    """Every all-reduce operand stays within ``allreduce_max_bytes``
    (the W-update psums move weight-matrix gradients and scalars — an
    all-reduce carrying feature-matrix-sized payload is a transport leak)."""
    budget = ctx.expectations.get("allreduce_max_bytes")
    if ctx.hlo_text is None or budget is None:
        return
    sizes = {ins.name: ins.result_bytes or ins.tuple_bytes
             for _, ins in ctx.instructions()}
    for comp, ins in _collective_instrs(ctx, "all-reduce"):
        nbytes = sum(sizes.get(o, 0) for o in ins.operands)
        if nbytes > budget:
            yield Finding(
                "collective/allreduce-payload", Severity.ERROR,
                f"all-reduce %{ins.name} moves {nbytes} B "
                f"> budget {budget} B",
                location=ins.name,
                details={"bytes": nbytes, "budget": int(budget),
                         "computation": comp.name})


@rule("collective/permute-schedule")
def permute_schedule(ctx: AnalysisContext) -> Iterable[Finding]:
    """The distinct ``source_target_pairs`` sets in the HLO equal the
    host-side exchange plan's per-round pair sets, both ways."""
    rounds = ctx.expectations.get("round_pairs")
    if ctx.hlo_text is None or not rounds:
        return
    want = {frozenset(tuple(p) for p in r) for r in rounds}
    got: set[frozenset] = set()
    for _, ins in _collective_instrs(ctx, "collective-permute"):
        pairs = H.permute_pairs(ins)
        if pairs:
            got.add(pairs)
    if not got:
        yield Finding(
            "collective/permute-schedule", Severity.ERROR,
            f"no collective-permute compiled but the host plan has "
            f"{len(want)} round(s)",
            details={"planned_rounds": sorted(sorted(r) for r in want)})
        return
    extra = got - want
    missing = want - got
    if extra:
        yield Finding(
            "collective/permute-schedule", Severity.ERROR,
            f"{len(extra)} compiled permute pair-set(s) not in the host "
            f"plan: {sorted(sorted(s) for s in extra)[:3]}",
            details={"unplanned": sorted(sorted(s) for s in extra)})
    if missing:
        yield Finding(
            "collective/permute-schedule", Severity.ERROR,
            f"{len(missing)} planned round(s) never compiled: "
            f"{sorted(sorted(s) for s in missing)[:3]}",
            details={"missing": sorted(sorted(s) for s in missing)})


@rule("collective/permute-count", severity=Severity.WARNING)
def permute_count(ctx: AnalysisContext) -> Iterable[Finding]:
    """collective-permute count ≈ rounds × gathers (XLA may merge or split
    permutes, so a mismatch is a warning, not an error)."""
    rounds = ctx.expectations.get("round_pairs")
    gathers = ctx.expectations.get("num_gathers")
    if ctx.hlo_text is None or not rounds or not gathers:
        return
    n = sum(1 for _ in _collective_instrs(ctx, "collective-permute"))
    want = len(rounds) * gathers
    if n != want:
        yield Finding(
            "collective/permute-count", Severity.WARNING,
            f"{n} collective-permute op(s) compiled, expected "
            f"{len(rounds)} round(s) x {gathers} gather(s) = {want}",
            details={"compiled": n, "expected": want})


@rule("collective/payload-budget")
def payload_budget(ctx: AnalysisContext) -> Iterable[Finding]:
    """Trip-weighted transport payload bytes (gather/permute/alltoall/
    reduce-scatter, per the census) stay within the scheduled wire bound
    from ``verify_transport_bytes``."""
    budget = ctx.expectations.get("collective_budget_bytes")
    if ctx.hlo_text is None or budget is None:
        return
    census = ctx.census()
    transport_ops = ("all-gather", "collective-permute", "all-to-all",
                     "reduce-scatter")
    moved = sum(census.collectives[op]["bytes"] for op in transport_ops)
    if moved > budget:
        yield Finding(
            "collective/payload-budget", Severity.ERROR,
            f"compiled transport payload {moved:.0f} B exceeds the "
            f"scheduled bound {budget} B",
            details={"bytes": moved, "budget": int(budget),
                     "per_op": {op: census.collectives[op]["bytes"]
                                for op in transport_ops}})
