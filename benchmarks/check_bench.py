"""Schema + regression checker for the repo-root BENCH_*.json artifacts.

CI runs ``benchmarks/run.py --quick`` (which emits the quick payloads and
calls this) so every push proves:

  * the artifacts parse and carry the fields the perf-trajectory tracking
    consumes (M, byte counters, per-epoch seconds);
  * the p2p exchange's scheduled wire bytes stay below the all-gather
    volume — the wire-byte win the neighbour-only exchange exists for;
  * the multilevel partitioner strictly beats the BFS+KL stand-in on edge
    cut at M=32 (no worse max_deg / wire bytes, strict balance) —
    partition quality is the lever behind every wire-byte number;
  * size-aware (bucketed) padding beats the global n_pad on the seed-0
    size-skewed power-law graph at M=32: lower pad bytes, lower pad FLOPs,
    and a row-exact p2p wire that undercuts the whole-block schedule and
    stays within the uniform-graph multilevel wire (m32_ragged).

Standalone: ``PYTHONPATH=src python benchmarks/check_bench.py [--root DIR]``
Exit code 0 = all checks pass; failures raise CheckError with the path of
the offending field.
"""
from __future__ import annotations

import argparse
import json
import numbers
import pathlib


class CheckError(AssertionError):
    pass


def _require(cond: bool, where: str, msg: str) -> None:
    if not cond:
        raise CheckError(f"{where}: {msg}")


def _fields(row: dict, spec: dict, where: str) -> None:
    for key, typ in spec.items():
        _require(key in row, where, f"missing field {key!r}")
        _require(isinstance(row[key], typ), where,
                 f"{key!r} should be {typ}, got {type(row[key]).__name__}")


def check_block_sparsity(payload: dict) -> None:
    where = "BENCH_block_sparsity"
    _fields(payload, {"quick": bool, "agg_sweep": list}, where)
    _require(len(payload["agg_sweep"]) >= 2, where, "agg_sweep too short")
    for i, r in enumerate(payload["agg_sweep"]):
        w = f"{where}.agg_sweep[{i}]"
        _fields(r, {"M": int, "nnz": int, "coll_full_kb": numbers.Real,
                    "coll_needed_kb": numbers.Real,
                    "coll_wire_kb": numbers.Real,
                    "p2p_rounds": int}, w)
        _require(r["coll_wire_kb"] <= r["coll_needed_kb"] + 1e-9, w,
                 f"p2p wire {r['coll_wire_kb']}k above the needed volume "
                 f"{r['coll_needed_kb']}k")
        _require(r["coll_needed_kb"] <= r["coll_full_kb"] + 1e-9, w,
                 "needed volume above the all-gather volume")


def check_speedup(payload: dict) -> None:
    where = "BENCH_speedup"
    _fields(payload, {"quick": bool, "rows": list, "m32_wire": dict,
                      "m32_partition": dict, "m32_ragged": dict,
                      "m32_packed": dict, "m32_minibatch": dict,
                      "m32_fused": dict}, where)
    modes = {r["mode"] for r in payload["rows"]}
    _require(modes == {"parallel"}, where,
             f"rows must cover the parallel trainer, got {sorted(modes)}")
    for i, r in enumerate(payload["rows"]):
        w = f"{where}.rows[{i}]"
        _fields(r, {"mode": str, "dataset": str,
                    "serial_per_epoch_s": numbers.Real,
                    "parallel_per_epoch_s": numbers.Real,
                    "parallel_collective_bytes": numbers.Real,
                    "adjacency_bytes": int}, w)
        _require(r["parallel_per_epoch_s"] > 0, w, "non-positive epoch time")
        _require(r["scheduled_wire_bytes"] <= r["comm_full_bytes"], w,
                 "scheduled wire bytes above the all-gather volume")
        _require(r["partitioner"] == "multilevel", w,
                 "row carries the wrong partitioner tag")
        _require(r["part_balance"] <= 1.0 + 1e-9, w,
                 "partition exceeds the strict balance cap")

    m32 = payload["m32_wire"]
    w = f"{where}.m32_wire"
    _fields(m32, {"M": int, "full_bytes": int, "needed_bytes": int,
                  "wire_bytes": int, "p2p_rounds": int,
                  "wire_reduction": numbers.Real}, w)
    _require(m32["M"] == 32, w, "wire comparison must be at M=32")
    _require(m32["wire_bytes"] < m32["full_bytes"], w,
             "p2p wire bytes not reduced vs allgather at M=32")
    _require(m32["wire_bytes"] <= m32["needed_bytes"], w,
             "p2p wire bytes above the mask-derived needed volume")

    # partitioner head-to-head at M=32 on the power-law benchmark graph:
    # the multilevel pass must strictly beat the BFS+KL stand-in on cut
    # (the acceptance criterion — cut IS the p2p wire volume) with no
    # worse ELL fan-in and no more scheduled wire bytes.
    mp = payload["m32_partition"]
    w = f"{where}.m32_partition"
    _fields(mp, {"M": int, "methods": dict}, w)
    _require(set(mp["methods"]) == {"bfs_kl", "multilevel"}, w,
             f"methods must cover bfs_kl/multilevel, "
             f"got {sorted(mp['methods'])}")
    for method, q in mp["methods"].items():
        _fields(q, {"edge_cut": int, "balance": numbers.Real,
                    "max_deg": int, "wire_bytes": int,
                    "p2p_rounds": int}, f"{w}.{method}")
        _require(q["balance"] <= 1.0 + 1e-9, f"{w}.{method}",
                 "partition exceeds the strict balance cap")
    kl, ml = mp["methods"]["bfs_kl"], mp["methods"]["multilevel"]
    _require(ml["edge_cut"] < kl["edge_cut"], w,
             f"multilevel cut {ml['edge_cut']} not strictly below bfs_kl "
             f"{kl['edge_cut']} at M=32")
    _require(ml["max_deg"] <= kl["max_deg"], w,
             f"multilevel max_deg {ml['max_deg']} worse than bfs_kl "
             f"{kl['max_deg']}")
    _require(ml["wire_bytes"] <= kl["wire_bytes"], w,
             f"multilevel wire {ml['wire_bytes']} above bfs_kl "
             f"{kl['wire_bytes']}")

    # ragged (size-aware) padding on the seed-0 size-skewed power-law graph
    # at M=32: bucketed padding must undercut the global-n_pad baseline on
    # pad bytes, pad FLOPs and scheduled wire, and the row-exact wire must
    # not exceed the uniform-graph multilevel wire above — proving the
    # global pad (not the size skew) was the communication cost.
    mr = payload["m32_ragged"]
    w = f"{where}.m32_ragged"
    _fields(mr, {"M": int, "size_skew": numbers.Real, "modes": dict}, w)
    _require(mr["M"] == 32, w, "ragged comparison must be at M=32")
    _require(set(mr["modes"]) == {"global", "bucketed"}, w,
             f"modes must cover global/bucketed, got {sorted(mr['modes'])}")
    for mode, q in mr["modes"].items():
        _fields(q, {"n_pad": int, "pad_rows": int, "pad_bytes": int,
                    "pad_flops": numbers.Real, "wire_bytes": int,
                    "true_wire_bytes": int, "p2p_rounds": int},
                f"{w}.{mode}")
    gl, bu = mr["modes"]["global"], mr["modes"]["bucketed"]
    _require(bu["pad_bytes"] < gl["pad_bytes"], w,
             f"bucketed pad_bytes {bu['pad_bytes']} not below global "
             f"{gl['pad_bytes']}")
    _require(bu["pad_flops"] < gl["pad_flops"], w,
             f"bucketed pad_flops {bu['pad_flops']} not below global "
             f"{gl['pad_flops']}")
    _require(bu["wire_bytes"] < gl["wire_bytes"], w,
             f"row-exact wire {bu['wire_bytes']} not below the whole-block "
             f"wire {gl['wire_bytes']}")
    _require(bu["wire_bytes"] <= ml["wire_bytes"], w,
             f"ragged wire {bu['wire_bytes']} on the skewed graph exceeds "
             f"the m32_partition multilevel wire {ml['wire_bytes']}")

    # packed resident state on the same skewed M=32 graph: the Σ-bucket-rows
    # plane must hold strictly fewer resident Z bytes than the strided
    # (M, n_pad, C) layout, and the staged exchange schedule must hide a
    # non-zero fraction of the wire behind per-arrival-group aggregation
    # (exposed wire strictly inside the total).
    pk = payload["m32_packed"]
    w = f"{where}.m32_packed"
    _fields(pk, {"M": int, "n_shards": int, "strided_rows": int,
                 "packed_rows": int, "bucket_rows": int,
                 "strided_z_bytes": int, "packed_z_bytes": int,
                 "resident_reduction": numbers.Real, "wire_bytes": int,
                 "overlap": dict, "roofline": dict}, w)
    _require(pk["M"] == 32, w, "packed comparison must be at M=32")
    _require(pk["packed_z_bytes"] < pk["strided_z_bytes"], w,
             f"packed resident Z {pk['packed_z_bytes']} not below strided "
             f"{pk['strided_z_bytes']}")
    _require(pk["bucket_rows"] <= pk["packed_rows"] <= pk["strided_rows"],
             w, "packed rows must sit between the Σ-bucket floor and the "
                "strided row count")
    ovl = pk["overlap"]
    _fields(ovl, {"num_rounds": int, "num_groups": int,
                  "overlap_efficiency": numbers.Real,
                  "total_wire_s": numbers.Real,
                  "exposed_wire_s": numbers.Real,
                  "exposed_wire_bytes": int}, f"{w}.overlap")
    _require(ovl["overlap_efficiency"] > 0, f"{w}.overlap",
             "staged exchange hides no wire (overlap_efficiency == 0)")
    _require(ovl["exposed_wire_s"] < ovl["total_wire_s"], f"{w}.overlap",
             "exposed wire not strictly inside the total scheduled wire")
    _require(ovl["exposed_wire_bytes"] <= pk["wire_bytes"], f"{w}.overlap",
             "exposed wire bytes above the scheduled wire volume")
    rf = pk["roofline"]
    _fields(rf, {"compute_s": numbers.Real, "memory_s": numbers.Real,
                 "collective_s": numbers.Real,
                 "collective_total_s": numbers.Real,
                 "collective_exposed_bytes": numbers.Real, "dominant": str},
            f"{w}.roofline")
    _require(rf["collective_s"] <= rf["collective_total_s"], f"{w}.roofline",
             "overlap-aware collective term above the total-wire pricing")

    # stochastic community minibatching on the same skewed M=32 graph:
    # the sampled rounds' restricted exchange and resident sweep must both
    # drop ≥2× vs full batch, the mean wire ratio must track the batch
    # fraction (round padding is the only legitimate excess), and the
    # staleness-decayed penalty must keep the sampled Lagrangian within
    # the pinned gap of the full-batch run after the same round count.
    mb = payload["m32_minibatch"]
    w = f"{where}.m32_minibatch"
    _fields(mb, {"M": int, "n_shards": int,
                 "batch_fraction": numbers.Real, "num_batches": int,
                 "schedule": list, "full_wire_bytes": int,
                 "sampled_wire_bytes": list,
                 "mean_sampled_wire_bytes": numbers.Real,
                 "wire_ratio": numbers.Real, "full_state_rows": int,
                 "sampled_state_rows": list,
                 "mean_sampled_state_rows": numbers.Real,
                 "state_ratio": numbers.Real,
                 "lagrangian_full": numbers.Real,
                 "lagrangian_minibatch": numbers.Real,
                 "lagrangian_0": numbers.Real,
                 "lagrangian_gap": numbers.Real}, w)
    _require(mb["M"] == 32, w, "minibatch comparison must be at M=32")
    _require(mb["mean_sampled_wire_bytes"] * 2 <= mb["full_wire_bytes"], w,
             f"mean sampled wire {mb['mean_sampled_wire_bytes']} not ≥2× "
             f"below the full-batch wire {mb['full_wire_bytes']}")
    _require(mb["wire_ratio"] <= mb["batch_fraction"] + 0.10, w,
             f"wire ratio {mb['wire_ratio']} above batch fraction "
             f"{mb['batch_fraction']} + slack")
    _require(mb["mean_sampled_state_rows"] * 2 <= mb["full_state_rows"], w,
             f"mean sampled sweep rows {mb['mean_sampled_state_rows']} not "
             f"≥2× below full batch {mb['full_state_rows']}")
    _require(mb["lagrangian_minibatch"] < mb["lagrangian_0"], w,
             "sampled run's Lagrangian did not descend from its start")
    _require(mb["lagrangian_gap"] <= 0.25, w,
             f"sampled Lagrangian gap {mb['lagrangian_gap']} above the "
             f"pinned 25% of the full-batch value")
    # every shard appears exactly once per sampler cycle — bounded
    # staleness is what the decay rule's convergence story rests on
    seen = sorted(s for b in mb["schedule"] for s in b)
    _require(seen == list(range(mb["n_shards"])), w,
             f"sampler cycle {mb['schedule']} does not cover every shard "
             f"exactly once")

    # fused aggregation→Z-update kernel: the fused step's aggregated
    # (k, n_pad, C) HBM intermediate must vanish (strictly below the
    # unfused write+read traffic), the traced-jaxpr aggregation→dot
    # handoff count must sit at the W-update floor of one per layer and
    # strictly below the unfused step's, and the fused-vs-unfused state
    # divergence (dot-order reassociation only) stays within the pinned
    # tolerance.
    fu = payload["m32_fused"]
    w = f"{where}.m32_fused"
    _fields(fu, {"M": int, "n_shards": int, "num_layers": int,
                 "agg_rows": int, "sites": int,
                 "unfused_intermediate_bytes": int,
                 "fused_intermediate_bytes": int,
                 "gemm_out_bytes": int,
                 "traffic_reduction": numbers.Real,
                 "parity_max_delta": numbers.Real,
                 "parity_tol": numbers.Real,
                 "fused_handoffs": int, "unfused_handoffs": int}, w)
    _require(fu["M"] == 32, w, "fused comparison must be at M=32")
    _require(fu["fused_intermediate_bytes"]
             < fu["unfused_intermediate_bytes"], w,
             f"fused intermediate HBM {fu['fused_intermediate_bytes']} not "
             f"below unfused {fu['unfused_intermediate_bytes']}")
    _require(fu["fused_intermediate_bytes"] == 0, w,
             "fused aggregate must never land in HBM (VMEM scratch only)")
    _require(fu["fused_handoffs"] <= fu["num_layers"], w,
             f"fused step hands {fu['fused_handoffs']} aggregates to dots — "
             f"above the W-update floor of {fu['num_layers']}")
    _require(fu["fused_handoffs"] < fu["unfused_handoffs"], w,
             f"fused handoffs {fu['fused_handoffs']} not below unfused "
             f"{fu['unfused_handoffs']}")
    _require(fu["parity_tol"] <= 1e-6, w,
             f"parity tolerance {fu['parity_tol']} looser than the pinned "
             f"1e-6")
    _require(fu["parity_max_delta"] <= fu["parity_tol"], w,
             f"fused-vs-unfused divergence {fu['parity_max_delta']} above "
             f"the pinned tolerance {fu['parity_tol']}")


def check_serving(payload: dict) -> None:
    where = "BENCH_serving"
    _fields(payload, {"quick": bool, "M": int, "num_nodes": int,
                      "zipf_s": numbers.Real, "batch": int,
                      "embed_capacity": int, "halo_capacity": int,
                      "hit": dict, "cold": dict,
                      "speedup_p50": numbers.Real, "parity": dict,
                      "hit_path": dict, "stats": dict}, where)
    _require(payload["M"] == 32, where, "serving bench must be at M=32")

    hit, cold = payload["hit"], payload["cold"]
    _fields(hit, {"p50_ms": numbers.Real, "p99_ms": numbers.Real,
                  "qps": numbers.Real, "hit_rate": numbers.Real,
                  "wire_bytes": int}, f"{where}.hit")
    _fields(cold, {"p50_ms": numbers.Real, "p99_ms": numbers.Real,
                   "qps": numbers.Real}, f"{where}.cold")
    # steady-state Zipf(1.1) traffic must land in cache — the floor the
    # whole engine exists to clear
    _require(hit["hit_rate"] >= 0.8, f"{where}.hit",
             f"steady-state hit rate {hit['hit_rate']} below the 0.8 floor")
    # tail of the cached path stays under the cold path's *median*
    _require(hit["p99_ms"] < cold["p50_ms"], where,
             f"cached p99 {hit['p99_ms']}ms not below the cold-path p50 "
             f"{cold['p50_ms']}ms")
    _require(payload["speedup_p50"] >= 5.0, where,
             f"cached p50 speedup {payload['speedup_p50']}x below the "
             f"pinned 5x")
    # the hit path moves nothing over a wire: the compiled gather program
    # has zero collectives and zero analyze errors
    _require(hit["wire_bytes"] == 0, f"{where}.hit",
             f"hit path moves {hit['wire_bytes']} wire bytes")
    hp = payload["hit_path"]
    _fields(hp, {"analysis_errors": int, "collectives": int,
                 "full_graph_rows_bound": int}, f"{where}.hit_path")
    _require(hp["collectives"] == 0, f"{where}.hit_path",
             f"{hp['collectives']} collective(s) in the compiled hit path")
    _require(hp["analysis_errors"] == 0, f"{where}.hit_path",
             f"{hp['analysis_errors']} analyze error(s) on the hit path")
    # cache-disabled baseline runs the same compiled programs: parity is
    # bitwise, not approximate
    par = payload["parity"]
    _fields(par, {"bitwise_equal": bool, "max_delta": numbers.Real,
                  "nodes": int}, f"{where}.parity")
    _require(par["bitwise_equal"] and par["max_delta"] == 0,
             f"{where}.parity",
             f"cached vs cache-disabled embeddings differ "
             f"(max_delta={par['max_delta']})")


CHECKS = {
    "BENCH_block_sparsity.json": check_block_sparsity,
    "BENCH_speedup.json": check_speedup,
    "BENCH_serving.json": check_serving,
}


def main(root: "str | None" = None) -> int:
    base = pathlib.Path(root) if root else \
        pathlib.Path(__file__).resolve().parents[1]
    for name, check in CHECKS.items():
        path = base / name
        if not path.exists():
            raise CheckError(f"{path} missing — run the emitting benchmark "
                             f"(benchmarks/run.py --quick)")
        check(json.loads(path.read_text()))
        print(f"[check_bench] {name}: OK")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=None,
                    help="directory holding the BENCH_*.json artifacts")
    raise SystemExit(main(root=ap.parse_args().root))
