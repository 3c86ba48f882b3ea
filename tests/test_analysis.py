"""repro.analysis unit tests: each rule must fire on a deliberately broken
program and stay silent on the blessed pattern.

The HLO-level rules are exercised on small canned HLO texts (no
compilation — these run in milliseconds); the jaxpr rule on traced
functions; the Pallas rules on hand-built and real kernel specs,
including the ISSUE's acceptance cases — an out-of-bounds index map, an
over-budget VMEM spec, and the estimate-vs-footprint parity bound.
"""
import numpy as np
import pytest

from repro import analysis
from repro.analysis.findings import Finding, Severity, Waiver, apply_waivers
from repro.analysis.rules.pallas import (VMEM_BUDGET_BYTES,
                                         check_kernel_bounds,
                                         check_kernel_vmem,
                                         check_tile_alignment)
from repro.analysis.rules.precision import check_jaxpr_precision
from repro.kernels.community_spmm import (BlockOperand, KernelSpec, ell_spec,
                                          spmm_spec)


def _hlo(body: str) -> str:
    return ("HloModule test\n\n"
            "ENTRY %main (p0: f32[8,8]) -> f32[8,8] {\n"
            + body + "\n}\n")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_lists_all_families():
    rules = analysis.all_rules()
    fams = {r.family for r in rules}
    assert {"collective", "memory", "precision", "pallas"} <= fams
    assert len({r.id for r in rules}) == len(rules)
    assert all(r.doc for r in rules), "every rule carries a docstring"


def test_rules_skip_on_empty_context():
    rep = analysis.analyze_hlo("", expectations={})
    assert rep.findings == []
    assert len(rep.rules_run) == len(analysis.all_rules())


# ---------------------------------------------------------------------------
# collective rules
# ---------------------------------------------------------------------------


def test_no_allgather_fires_only_under_p2p():
    text = _hlo(
        "  %p0 = f32[8,8]{1,0} parameter(0)\n"
        "  ROOT %ag = f32[16,8]{1,0} all-gather(f32[8,8]{1,0} %p0), "
        "dimensions={0}")
    bad = analysis.analyze_hlo(text, expectations={"transport": "p2p"})
    assert bad.findings_for("collective/no-allgather-under-p2p")
    ok = analysis.analyze_hlo(text, expectations={"transport": "allgather"})
    assert not ok.findings_for("collective/no-allgather-under-p2p")


def test_permute_schedule_matches_host_plan():
    text = _hlo(
        "  %p0 = f32[8,8]{1,0} parameter(0)\n"
        "  ROOT %cp = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %p0), "
        "source_target_pairs={{0,1},{1,0}}")
    ok = analysis.analyze_hlo(
        text, expectations={"round_pairs": [((0, 1), (1, 0))]})
    assert not ok.findings_for("collective/permute-schedule")
    # a round the host never scheduled, and a scheduled round that never
    # compiled, are both errors
    bad = analysis.analyze_hlo(
        text, expectations={"round_pairs": [((0, 1),), ((1, 0),)]})
    msgs = [f.message for f in bad.findings_for("collective/permute-schedule")]
    assert any("not in the host plan" in m for m in msgs)
    assert any("never compiled" in m for m in msgs)
    none = analysis.analyze_hlo(
        _hlo("  ROOT %p0 = f32[8,8]{1,0} parameter(0)"),
        expectations={"round_pairs": [((0, 1),)]})
    assert none.findings_for("collective/permute-schedule")


def test_allreduce_payload_budget():
    text = _hlo(
        "  %p0 = f32[8,8]{1,0} parameter(0)\n"
        "  ROOT %ar = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %p0), "
        "to_apply=%add")
    ok = analysis.analyze_hlo(text,
                              expectations={"allreduce_max_bytes": 4096})
    assert not ok.findings_for("collective/allreduce-payload")
    bad = analysis.analyze_hlo(text,
                               expectations={"allreduce_max_bytes": 16})
    assert bad.findings_for("collective/allreduce-payload")


# ---------------------------------------------------------------------------
# memory rules
# ---------------------------------------------------------------------------


def test_dense_adjacency_intermediate_is_flagged():
    exp = {"n_pad": 16, "lanes": 1, "max_deg": 2, "m_total": 4}
    # a computed (4, 16, 16) block stack: 4 blocks > lanes*max_deg = 2
    text = _hlo(
        "  %p0 = f32[4,16,16]{2,1,0} parameter(0)\n"
        "  ROOT %b = f32[4,16,16]{2,1,0} broadcast(f32[4,16,16]{2,1,0} %p0), "
        "dimensions={0,1,2}")
    bad = analysis.analyze_hlo(text, expectations=exp)
    hits = bad.findings_for("memory/no-dense-adjacency")
    assert len(hits) == 1 and hits[0].location == "b"
    # the parameter itself is within the full-M ELL store bound (4*2=8)
    assert not any(f.location == "p0" for f in hits)
    # the dense baseline waives the pattern wholesale
    ok = analysis.analyze_hlo(
        text, expectations=dict(exp, dense_adjacency_allowed=True))
    assert not ok.findings_for("memory/no-dense-adjacency")


def test_hbm_budget_and_host_transfer():
    text = _hlo(
        "  %p0 = f32[1024,1024]{1,0} parameter(0)\n"
        "  ROOT %e = f32[1024,1024]{1,0} exponential(f32[1024,1024]{1,0} "
        "%p0)")
    bad = analysis.analyze_hlo(
        text, expectations={"hbm_intermediate_budget": 1 << 20})
    assert bad.findings_for("memory/hbm-intermediate-budget")
    ok = analysis.analyze_hlo(
        text, expectations={"hbm_intermediate_budget": 1 << 23})
    assert not ok.findings_for("memory/hbm-intermediate-budget")

    outfeed = _hlo(
        "  %p0 = f32[8,8]{1,0} parameter(0)\n"
        "  ROOT %o = token[] outfeed(f32[8,8]{1,0} %p0)")
    assert analysis.analyze_hlo(outfeed).findings_for(
        "memory/host-transfer")


def test_donated_inputs_rule():
    exp = {"expect_donated": (".zs", ".u"),
           "args_donated": {"[0].zs[0]": True, "[0].zs[1]": False,
                            "[0].u": True, "[0].taus[0]": False}}
    rep = analysis.analyze_hlo("", expectations=exp)
    hits = rep.findings_for("memory/donated-inputs")
    assert len(hits) == 1 and ".zs" in hits[0].message
    clean = analysis.analyze_hlo("", expectations={
        "expect_donated": (".zs",), "args_donated": {"[0].zs[0]": True}})
    assert not clean.findings_for("memory/donated-inputs")
    # a stale expectation (no matching arg at all) is a warning
    stale = analysis.analyze_hlo("", expectations={
        "expect_donated": (".zq",), "args_donated": {"[0].zs[0]": True}})
    hits = stale.findings_for("memory/donated-inputs")
    assert hits and hits[0].severity == Severity.WARNING


# ---------------------------------------------------------------------------
# precision rules
# ---------------------------------------------------------------------------


def test_bf16_dot_without_f32_accumulate_is_flagged():
    bad = _hlo(
        "  %a = bf16[8,8]{1,0} parameter(0)\n"
        "  %b = bf16[8,8]{1,0} parameter(1)\n"
        "  ROOT %d = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} "
        "%b), lhs_contracting_dims={1}, rhs_contracting_dims={0}")
    rep = analysis.analyze_hlo(bad)
    assert rep.findings_for("precision/bf16-dot-accumulate")
    # the blessed pattern: f32 result dot over bf16 operands
    good = _hlo(
        "  %a = bf16[8,8]{1,0} parameter(0)\n"
        "  %b = bf16[8,8]{1,0} parameter(1)\n"
        "  ROOT %d = f32[8,8]{1,0} dot(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} "
        "%b), lhs_contracting_dims={1}, rhs_contracting_dims={0}")
    assert not analysis.analyze_hlo(good).findings_for(
        "precision/bf16-dot-accumulate")


def test_f64_leak_is_flagged_unless_allowed():
    text = _hlo("  ROOT %c = f64[4]{0} constant({1, 2, 3, 4})")
    assert analysis.analyze_hlo(text).findings_for("precision/no-f64")
    ok = analysis.analyze_hlo(text, expectations={"allow_f64": True})
    assert not ok.findings_for("precision/no-f64")


def test_jaxpr_dataflow_catches_missing_f32_accumulate():
    import jax
    import jax.numpy as jnp

    def bad(a, b):
        return jax.lax.dot(a, b)                    # bf16 accumulate

    def good(a, b):
        return jax.lax.dot(a, b,
                           preferred_element_type=jnp.float32)

    a = jnp.zeros((8, 8), jnp.bfloat16)
    findings = check_jaxpr_precision(jax.make_jaxpr(bad)(a, a))
    assert any(f.rule == "precision/jaxpr-dataflow"
               and f.severity == Severity.ERROR for f in findings)
    assert not check_jaxpr_precision(jax.make_jaxpr(good)(a, a))


# ---------------------------------------------------------------------------
# pallas kernel rules (ISSUE acceptance: OOB index map, over-budget VMEM,
# estimate-vs-footprint parity)
# ---------------------------------------------------------------------------


def _toy_spec(index_map, *, grid=(4, 2), blocks=(64, 128),
              array=(256, 256)):
    return KernelSpec(
        name="toy", grid=grid,
        operands=(BlockOperand("x", array, blocks, index_map),),
        scratch_bytes=0)


def test_oob_index_map_is_flagged():
    # block row 4 of 4 — one past the end on the last grid step
    bad = _toy_spec(lambda i, j: (i + 1, j))
    findings = check_kernel_bounds(bad)
    assert findings and findings[0].rule == "pallas/index-bounds"
    assert "out of range" in findings[0].message
    ok = _toy_spec(lambda i, j: (i, j))
    assert not check_kernel_bounds(ok)


def test_oob_scalar_prefetch_indices_are_flagged():
    # 6 communities but an ELL index pointing at community 9
    spec = ell_spec(k=2, max_deg=2, n_pad=16, c=16, m_total=6)
    good = {"ell_indices": np.array([[0, 5], [1, 2]], np.int32),
            "ell_mask": np.ones((2, 2), np.int32),
            "row_counts": np.full((2,), 16, np.int32),
            "nbr_counts": np.full((2, 2), 16, np.int32)}
    assert not check_kernel_bounds(spec, good)
    bad = dict(good, ell_indices=np.array([[0, 9], [1, 2]], np.int32))
    findings = check_kernel_bounds(spec, bad)
    assert findings and findings[0].rule == "pallas/index-bounds"
    assert "out of range" in findings[0].message
    assert findings[0].details["index"] == 9


def test_over_budget_vmem_spec_is_flagged():
    # 2 MiB blocks, double-buffered -> 4 MiB > a 1 MiB budget
    big = _toy_spec(lambda i, j: (i, j), blocks=(512, 1024),
                    array=(2048, 2048), grid=(4, 2))
    findings = check_kernel_vmem(big, budget=1 << 20)
    assert findings and findings[0].rule == "pallas/vmem-budget"
    assert not check_kernel_vmem(big)   # default 16 MiB budget fits


def test_ell_vmem_estimate_within_2x_of_spec_footprint():
    """Parity: the linter's VMEM estimate stays within [1x, 2x] of the
    single-buffered footprint derived from the same spec (the factor is
    the pipeline double-buffering)."""
    for k, max_deg, n_pad, c, m in [(2, 2, 256, 256, 8), (4, 3, 512, 64, 16),
                                    (1, 1, 128, 128, 4)]:
        spec = ell_spec(k, max_deg, n_pad, c, m)
        footprint = (sum(op.block_bytes() for op in spec.operands)
                     + spec.scratch_bytes)
        est = spec.vmem_bytes()
        assert footprint <= est <= 2 * footprint, (spec.name, est, footprint)
        assert est <= VMEM_BUDGET_BYTES, "benchmark tiles must fit VMEM"


def test_real_kernel_specs_pass_all_pallas_rules():
    """The shipped kernels' own specs are clean under every Pallas rule —
    the same check analyze_trainer runs on benchmark configs."""
    d = spmm_spec(m=8, n_pad=256, c=256)
    assert not check_kernel_bounds(d)
    assert not check_kernel_vmem(d)
    assert not check_tile_alignment(d)
    e = ell_spec(k=2, max_deg=3, n_pad=256, c=256, m_total=8)
    scalars = {"ell_indices": np.zeros((2, 3), np.int32),
               "ell_mask": np.ones((2, 3), np.int32),
               "row_counts": np.full((2,), 256, np.int32),
               "nbr_counts": np.full((2, 3), 256, np.int32)}
    assert not check_kernel_bounds(e, scalars)
    assert not check_kernel_vmem(e)
    assert not check_tile_alignment(e)


def test_tile_alignment_warns_on_ragged_blocks():
    # 100 is neither 128-aligned nor the full dim
    bad = _toy_spec(lambda i, j: (0, 0), blocks=(64, 100),
                    array=(256, 400), grid=(1, 1))
    findings = check_tile_alignment(bad)
    # Mosaic refuses such a block outright: an error, not a warning
    assert findings and findings[0].severity == Severity.ERROR


# ---------------------------------------------------------------------------
# findings / report plumbing
# ---------------------------------------------------------------------------


def test_waiver_mutes_matching_configs_only():
    f = Finding("memory/no-dense-adjacency", Severity.ERROR, "boom")
    w = Waiver("memory/no-dense-adjacency", "dense baseline",
               when={"compressed": False})
    kept, waived = apply_waivers([f], {"compressed": False}, [w])
    assert not kept and len(waived) == 1
    kept, waived = apply_waivers([f], {"compressed": True}, [w])
    assert len(kept) == 1 and not waived


def test_no_findings_severity_threshold():
    warn = Finding("precision/bf16-reduce", Severity.WARNING, "w")
    err = Finding("precision/no-f64", Severity.ERROR, "e")
    assert analysis.no_findings([warn], min_severity=Severity.ERROR)
    assert not analysis.no_findings([warn])
    assert not analysis.no_findings([warn, err], rule="precision/no-f64",
                                    min_severity=Severity.ERROR)
    assert analysis.no_findings([err], rule="precision/bf16-reduce")


def test_report_json_round_trip():
    import json

    rep = analysis.analyze_hlo(
        _hlo("  ROOT %c = f64[4]{0} constant({1, 2, 3, 4})"),
        config="rt", expectations={"n_pad": 8})
    with pytest.raises(AssertionError):
        rep.assert_no_findings()
    blob = json.loads(rep.to_json())
    assert blob["config"] == "rt"
    assert blob["findings"][0]["rule"] == "precision/no-f64"
    assert blob["findings"][0]["severity"] == "error"
    assert blob["expectations"]["n_pad"] == 8
