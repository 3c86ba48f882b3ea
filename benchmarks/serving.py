"""Serving latency under Zipf traffic: the community cache pays.

Trains a small community model on the size-skewed M=32 power-law graph
(the benchmark graph every layout/transport number is measured on),
builds a ``serve.CommunityServer`` over it, and fires a Zipf(1.1) node
request stream — the heavy-tailed "millions of users" traffic shape —
through the batched serving path twice:

  * **cached** — embedding + halo caches at the pinned capacities with
    Zipf-aware admission; steady-state batches are answered by per-block
    row gathers;
  * **cold** — ``ServeConfig(cache_enabled=False)``: the same compiled
    programs with capacity-0 caches, so every batch recomputes its
    communities' L-hop chains through the packed kernels.  Bitwise
    parity between the two paths is asserted on a probe set.

Reports p50/p99 per-batch latency, QPS and steady-state hit rate as the
repo-root ``BENCH_serving.json`` (CI artifact, guarded by
benchmarks/check_bench.py: hit-rate floor, cached p99 below the cold
p50, ≥5× p50 speedup, zero-collective hit path).

  PYTHONPATH=src python benchmarks/serving.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

ZIPF_S = 1.1
M = 32
BATCH = 64
EMBED_CAPACITY = 40
HALO_CAPACITY = 64


def _percentiles(times_s: list) -> dict:
    arr = np.asarray(times_s, dtype=np.float64) * 1e3
    return {"p50_ms": round(float(np.percentile(arr, 50)), 4),
            "p99_ms": round(float(np.percentile(arr, 99)), 4)}


def _run_stream(server, stream: np.ndarray, batch: int, warmup_frac: float
                ) -> dict:
    """Serve the stream in batches; steady-state timing past the warmup."""
    n_batches = len(stream) // batch
    warmup = max(int(n_batches * warmup_frac), 1)
    times, served = [], 0
    hits0 = total0 = 0
    for i in range(n_batches):
        ids = stream[i * batch:(i + 1) * batch]
        if i == warmup:
            hits0, total0 = server.request_hits, server.request_total
        t0 = time.perf_counter()
        server.serve(ids)
        dt = time.perf_counter() - t0
        if i >= warmup:
            times.append(dt)
            served += len(ids)
    steady_total = server.request_total - total0
    steady_hits = server.request_hits - hits0
    out = _percentiles(times)
    out["qps"] = round(served / max(sum(times), 1e-9), 1)
    out["hit_rate"] = round(steady_hits / max(steady_total, 1), 4)
    out["batches"] = len(times)
    out["warmup_batches"] = warmup
    return out


def _hit_path_report(server) -> dict:
    """Prove the steady-state hit program is collective-free and touches
    nothing full-graph-sized (the same expectations the ``serve_hit``
    analyze config pins in CI)."""
    from repro import analysis
    from repro.analysis import hlo as hlo_mod

    text = server.hit_path_lowered(bucket=BATCH).compile().as_text()
    bound = int(server.dl.plane_rows)
    rep = analysis.analyze_hlo(text, expectations={
        "expect_zero_collectives": True,
        "full_graph_rows": bound,
    }, config="serve_hit")
    census = hlo_mod.hlo_census(text)
    n_coll = sum(v["count"] for v in census.collectives.values())
    return {"analysis_errors": len(rep.errors()),
            "collectives": int(n_coll),
            "full_graph_rows_bound": bound,
            # single-device, zero collectives compiled: nothing crosses
            # a wire on the hit path — the quantity check_bench pins
            "wire_bytes": 0 if n_coll == 0 else -1}


def run(quick: bool = False) -> dict:
    import jax

    from repro.core import gcn, graph
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
    from repro.core.subproblems import ADMMConfig
    from repro.serve import CommunityServer, ServeConfig, zipf_node_stream

    epochs = 2 if quick else 5
    requests = 1920 if quick else 6400
    cold_requests = 640 if quick else 1280

    g, part = graph.synthetic_powerlaw_communities(
        M, nodes_per_part=32, attach=2, seed=0, feat_dim=16, size_skew=1.0)
    cfg = gcn.GCNConfig(layer_dims=(16, 32, g.num_classes))
    tr = ParallelADMMTrainer(
        cfg, ADMMConfig(nu=1e-3, rho=1e-3), g, num_parts=M, seed=0,
        part=part, config=TrainerConfig.packed())
    tr.train(epochs)
    train_acc, test_acc, _ = (float(x) for x in tr._metrics(tr.state))

    stream = zipf_node_stream(g.num_nodes, requests, s=ZIPF_S, seed=1)

    served_cfg = ServeConfig(embed_capacity=EMBED_CAPACITY,
                             halo_capacity=HALO_CAPACITY, admission="zipf",
                             max_batch=BATCH)
    server = CommunityServer.from_trainer(tr, served_cfg)
    hit = _run_stream(server, stream, BATCH, warmup_frac=0.25)
    hit_path = _hit_path_report(server)
    hit["wire_bytes"] = hit_path["wire_bytes"]

    cold_cfg = ServeConfig(embed_capacity=EMBED_CAPACITY,
                           halo_capacity=HALO_CAPACITY, admission="zipf",
                           max_batch=BATCH, cache_enabled=False)
    cold_server = CommunityServer.from_trainer(tr, cold_cfg)
    cold = _run_stream(cold_server, stream[:cold_requests], BATCH,
                       warmup_frac=0.25)
    cold.pop("hit_rate", None)

    # parity: the same probe nodes through both engines, bitwise
    probe = np.unique(stream[:512])
    a = server.serve(probe)
    b = cold_server.serve(probe)
    parity = {"bitwise_equal": bool(np.array_equal(a, b)),
              "max_delta": float(np.abs(a - b).max()),
              "nodes": int(len(probe))}

    jax.block_until_ready(server.z0_plane)
    return {
        "quick": bool(quick),
        "M": M,
        "num_nodes": int(g.num_nodes),
        "zipf_s": ZIPF_S,
        "requests": int(requests),
        "batch": BATCH,
        "embed_capacity": EMBED_CAPACITY,
        "halo_capacity": HALO_CAPACITY,
        "admission": "zipf",
        "train": {"epochs": epochs, "train_acc": round(train_acc, 4),
                  "test_acc": round(test_acc, 4)},
        "hit": hit,
        "cold": cold,
        "speedup_p50": round(cold["p50_ms"] / max(hit["p50_ms"], 1e-9), 2),
        "parity": parity,
        "hit_path": hit_path,
        "stats": server.stats(),
    }


def main(quick: bool = False, out: "str | None" = None) -> dict:
    payload = run(quick=quick)
    path = pathlib.Path(out) if out else \
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_serving.json"
    path.write_text(json.dumps(payload, indent=2))
    h, c = payload["hit"], payload["cold"]
    print(f"[serving] hit_rate={h['hit_rate']} p50={h['p50_ms']}ms "
          f"p99={h['p99_ms']}ms qps={h['qps']} | cold p50={c['p50_ms']}ms "
          f"| speedup_p50={payload['speedup_p50']}x "
          f"| parity={payload['parity']['bitwise_equal']}")
    print(f"wrote {path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer requests/epochs (CI smoke)")
    ap.add_argument("--out", default=None, help="output JSON path")
    args = ap.parse_args()
    main(quick=args.quick, out=args.out)
