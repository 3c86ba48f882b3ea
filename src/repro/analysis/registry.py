"""Rule registry and the analysis context rules run against.

A rule is a function ``(AnalysisContext) -> Iterable[Finding]`` registered
under a stable id (``family/name``).  Rules must *skip* (yield nothing)
when the context lacks what they inspect — an HLO rule on a jaxpr-only
context is vacuous, not an error — so one registry serves every entry
point (trainer analysis, canned-HLO unit tests, kernel-spec lints).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Mapping, Optional, Sequence

from repro.analysis import hlo as hlo_mod
from repro.analysis.findings import (Finding, Report, Severity, Waiver,
                                     apply_waivers)

RuleFn = Callable[["AnalysisContext"], Iterable[Finding]]


@dataclasses.dataclass
class AnalysisContext:
    """What a rule may inspect.  Any field may be None/empty; rules skip
    what is absent.

    expectations — facts about the config under analysis that rules
    check the program against.  Keys used by the built-in rules:

      n_shards                 shards of a trainer step: arms the
                               no-all-gather rule; > 1 arms the
                               packed-resident-state rule
      packed_rows_bound        tallest blocked row stack (the r_pad view)
      round_pairs              list of per-round frozensets of (src, dst)
      num_gathers              host-side gathers per trainer step
      collective_budget_bytes  bound on transport payload bytes (census)
      allreduce_max_bytes      bound on any single all-reduce operand
      m_total, lanes, n_pad, max_deg   layout facts for the dense-adjacency
                               pattern matcher
      hbm_intermediate_budget  bound on any single intermediate's bytes
      args_donated             {arg_path: bool} from lowered.args_info
      expect_donated           substrings of arg paths that must be donated
      allow_f64                True to mute the f64-leak rule
      kernels                  list of kernel-spec dicts for Pallas rules
    """
    hlo_text: Optional[str] = None
    jaxpr: Any = None                  # jax.core.ClosedJaxpr or None
    expectations: dict[str, Any] = dataclasses.field(default_factory=dict)
    config: str = ""
    _comps: Optional[dict[str, hlo_mod.Computation]] = \
        dataclasses.field(default=None, repr=False)

    @property
    def computations(self) -> dict[str, hlo_mod.Computation]:
        if self._comps is None:
            self._comps = hlo_mod.parse_hlo(self.hlo_text or "")
        return self._comps

    def instructions(self):
        return hlo_mod.iter_instructions(self.computations)

    def census(self) -> hlo_mod.Census:
        return hlo_mod.hlo_census(self.hlo_text or "")


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    fn: RuleFn
    severity: Severity                 # default severity, shown in catalogue
    doc: str

    @property
    def family(self) -> str:
        return self.id.split("/", 1)[0]


_REGISTRY: dict[str, Rule] = {}


def rule(id: str, *, severity: Severity = Severity.ERROR
         ) -> Callable[[RuleFn], RuleFn]:
    """Register a rule under ``id`` (``family/name``)."""
    def deco(fn: RuleFn) -> RuleFn:
        doc = (fn.__doc__ or "").strip().splitlines()
        _REGISTRY[id] = Rule(id, fn, severity, doc[0] if doc else "")
        return fn
    return deco


def get_rule(id: str) -> Rule:
    _ensure_builtin_rules()
    return _REGISTRY[id]


def all_rules(family: Optional[str] = None) -> list[Rule]:
    _ensure_builtin_rules()
    rules = sorted(_REGISTRY.values(), key=lambda r: r.id)
    if family is not None:
        rules = [r for r in rules if r.family == family]
    return rules


def _ensure_builtin_rules() -> None:
    # rule modules self-register on import; idempotent
    from repro.analysis.rules import (collective, memory,  # noqa: F401
                                      pallas, precision)


def run_rules(ctx: AnalysisContext,
              rules: Optional[Sequence[str]] = None,
              waivers: Sequence[Waiver] = (),
              families: Optional[Sequence[str]] = None) -> Report:
    """Run (a subset of) the registry against ``ctx`` and build a Report."""
    _ensure_builtin_rules()
    if rules is not None:
        picked = [get_rule(r) for r in rules]
    else:
        picked = all_rules()
        if families is not None:
            fams = set(families)
            picked = [r for r in picked if r.family in fams]
    found: list[Finding] = []
    for r in picked:
        found.extend(r.fn(ctx))
    kept, muted = apply_waivers(found, ctx.expectations, waivers)
    return Report(config=ctx.config,
                  expectations=dict(ctx.expectations),
                  findings=kept, waived=muted,
                  rules_run=[r.id for r in picked])


def analyze_hlo(hlo_text: str,
                expectations: Optional[Mapping[str, Any]] = None,
                *, config: str = "",
                rules: Optional[Sequence[str]] = None,
                waivers: Sequence[Waiver] = ()) -> Report:
    """Lint a compiled-HLO dump against ``expectations``."""
    ctx = AnalysisContext(hlo_text=hlo_text,
                          expectations=dict(expectations or {}),
                          config=config)
    return run_rules(ctx, rules=rules, waivers=waivers)
