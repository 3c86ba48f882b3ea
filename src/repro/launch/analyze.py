"""CLI: run the invariant linter over the benchmark trainer configs.

Builds each benchmark trainer (transport x pad-mode on the compressed
layout, plus the dense baseline in full mode), compiles its step on a
4-shard host mesh, runs the ``repro.analysis`` rule registry against the
trainer's own host-side expectations, and writes a JSON report.  Exit
status 1 if any error-severity finding survives its waivers — CI fails
the build on that.

    PYTHONPATH=src python src/repro/launch/analyze.py --quick
    PYTHONPATH=src python src/repro/launch/analyze.py --out report.json

The device-count flag must be set before jax initialises (a 1-shard mesh
compiles no real collectives, which would make every transport rule
vacuous), so jax/repro imports happen inside ``main`` after the env is
prepared.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

N_SHARDS = 4

# the four benchmark transport x pad-mode configs (--quick and CI);
# full mode adds the dense baseline (the dense-adjacency rule is waived
# there — that config IS the dense layout) and the bf16 wire/store path
QUICK_CONFIGS = [
    {"name": "p2p_global", "transport": "p2p", "pad_mode": "global"},
    {"name": "p2p_bucketed", "transport": "p2p", "pad_mode": "bucketed"},
    {"name": "allgather_global", "transport": "allgather",
     "pad_mode": "global"},
    {"name": "allgather_bucketed", "transport": "allgather",
     "pad_mode": "bucketed"},
    # packed resident state: memory/packed-resident-state proves the
    # compiled step holds no blocked row stack taller than r_pad
    {"name": "p2p_packed", "transport": "p2p", "pad_mode": "bucketed",
     "packed": True},
    {"name": "p2p_packed_overlap", "transport": "p2p",
     "pad_mode": "bucketed", "packed": True, "overlap": True},
    # stochastic minibatching: the collective/permute-schedule rule proves
    # the compiled sampled step's ppermute pairs are exactly the
    # restricted sub-plan's — no collective touches an unsampled shard pair
    {"name": "p2p_minibatch", "transport": "p2p", "pad_mode": "bucketed",
     "packed": True, "batch_fraction": 0.5, "stale_decay": 0.5},
    # fused aggregation→Z-update: memory/fused-no-intermediate proves the
    # compiled step hands no aggregated (k, n_pad, C) stack to a GEMM
    # beyond the W-update line-search allowance, and the pallas VMEM rule
    # covers the fused spec's scratch-resident aggregate
    {"name": "p2p_fused", "transport": "p2p", "pad_mode": "bucketed",
     "packed": True, "fused": True},
]
FULL_CONFIGS = QUICK_CONFIGS + [
    {"name": "dense_allgather", "transport": "allgather",
     "pad_mode": "global", "compressed": False},
    {"name": "p2p_bf16", "transport": "p2p", "pad_mode": "bucketed",
     "comm_bf16": True, "adjacency_bf16": True},
]


# serving-engine programs (repro.serve): the steady-state hit path must
# compile with zero collectives and nothing full-graph-sized (a hit
# touches one community block + one request-row vector); the miss-path
# halo kernel legitimately reads the Σ-bucket-rows plane but must still
# be collective-free (single-device recompute)
SERVE_CONFIGS = ["serve_hit", "serve_halo"]


def _ensure_devices() -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_SHARDS}"
        ).strip()


def _build_trainer(spec: dict):
    import jax

    from repro.core import gcn, graph
    from repro.core.parallel import AXIS, ParallelADMMTrainer, TrainerConfig
    from repro.core.subproblems import ADMMConfig
    from jax.sharding import AxisType

    g, part = graph.synthetic_powerlaw_communities(
        num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    mesh = jax.make_mesh((N_SHARDS,), (AXIS,), (AxisType.Auto,),
                         devices=jax.devices()[:N_SHARDS])
    # the spec dicts ARE TrainerConfig kwargs (single source of truth);
    # only the compressed default differs from the dataclass default
    kw = {k: v for k, v in spec.items() if k != "name"}
    kw.setdefault("compressed", True)
    return ParallelADMMTrainer(cfg, admm, g, num_parts=8, seed=0,
                               part=part, mesh=mesh,
                               config=TrainerConfig(**kw))


def run_configs(configs: list[dict]) -> list:
    from repro import analysis

    # the dense baseline legitimately holds the dense block tensor; the
    # rule is already gated on dense_adjacency_allowed, the waiver here
    # documents the intent in the report
    waivers = (analysis.Waiver(
        "memory/no-dense-adjacency",
        "the dense baseline IS the dense layout",
        when={"compressed": False}),)
    reports = []
    for spec in configs:
        tr = _build_trainer(spec)
        reports.append(analysis.analyze_trainer(
            tr, config=spec["name"], waivers=waivers))
    return reports


def _build_server():
    import jax

    from repro.core import gcn, graph
    from repro.serve import CommunityServer, ServeConfig

    g, part = graph.synthetic_powerlaw_communities(
        num_parts=8, nodes_per_part=12, attach=1, seed=0, feat_dim=8,
        size_skew=0.8)
    cfg = gcn.GCNConfig(layer_dims=(8, 8, g.num_classes))
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed", num_parts=8)
    ws = gcn.init_weights(cfg, jax.random.key(0))
    return CommunityServer(cfg, layout, ws, g.features, ServeConfig())


def run_serving_configs(names=None) -> list:
    from repro import analysis

    picked = set(names) if names else set(SERVE_CONFIGS)
    srv = _build_server()
    reports = []
    if "serve_hit" in picked:
        hlo = srv.hit_path_lowered(bucket=64).compile().as_text()
        reports.append(analysis.analyze_hlo(
            hlo, expectations={
                "expect_zero_collectives": True,
                "full_graph_rows": int(srv.dl.plane_rows),
            }, config="serve_hit"))
    if "serve_halo" in picked:
        hlo = srv.halo_path_lowered(layer=1).compile().as_text()
        reports.append(analysis.analyze_hlo(
            hlo, expectations={"expect_zero_collectives": True},
            config="serve_halo"))
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="invariant linter over the benchmark trainer configs")
    ap.add_argument("--quick", action="store_true",
                    help="the four transport x pad-mode configs only")
    ap.add_argument("--config", action="append", default=None,
                    help="run only the named config(s)")
    ap.add_argument("--out", default="BENCH_analysis.json",
                    help="JSON report path")
    args = ap.parse_args(argv)

    _ensure_devices()
    configs = QUICK_CONFIGS if args.quick else FULL_CONFIGS
    serve_names = list(SERVE_CONFIGS)
    if args.config:
        picked = set(args.config)
        unknown = picked - {c["name"] for c in configs} - set(SERVE_CONFIGS)
        if unknown:
            ap.error(f"unknown config(s): {sorted(unknown)}")
        configs = [c for c in configs if c["name"] in picked]
        serve_names = [n for n in SERVE_CONFIGS if n in picked]

    reports = run_configs(configs)
    if serve_names:
        reports.extend(run_serving_configs(serve_names))
    n_err = 0
    for rep in reports:
        print(rep.summary())
        n_err += len(rep.errors())
    payload = {"n_shards": N_SHARDS,
               "errors": n_err,
               "reports": [r.to_dict() for r in reports]}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    print(f"wrote {args.out}: {len(reports)} config(s), "
          f"{n_err} error finding(s)")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir))
    sys.exit(main())
