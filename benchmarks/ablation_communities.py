"""Beyond-paper ablation: number of communities M vs accuracy / edge cut /
communication volume / per-agent compute.

The paper fixes M=3.  Each M runs in a subprocess with M host devices (one
per agent), so the collective census and per-device FLOPs reflect a real
M-agent deployment: per-agent compute shrinks ~1/M while the gathered
message volume and the edge cut grow — the trade-off the paper's community
splitting navigates.

Every row additionally reports the partition-quality head-to-head
(edge_cut / balance / max_deg) of both ``partition_graph`` methods at that
M — ``bfs_kl`` (the original stand-in) vs ``multilevel``
(sharding.multilevel, the METIS-scheme pass the trainer now defaults to
here via ``--partitioner``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.speedup import cpu_host_env  # noqa: E402

WORKER = textwrap.dedent("""
    import json, sys
    import jax
    import numpy as np
    from repro.core import gcn, graph
    from repro.core.subproblems import ADMMConfig
    from repro.core.parallel import ParallelADMMTrainer
    from repro.launch import roofline
    dataset, m, epochs, hidden, partitioner = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
        sys.argv[5])
    g = graph.synthetic_sbm(dataset, seed=0)
    hyper = 1e-3 if "computers" in dataset else 1e-4
    cfg = gcn.GCNConfig(layer_dims=(g.features.shape[1], hidden,
                                    g.num_classes))
    tr = ParallelADMMTrainer(cfg, ADMMConfig(nu=hyper, rho=hyper), g,
                             num_parts=m, seed=0, partitioner=partitioner)
    # partition-quality head-to-head at this M: the cut sets the message
    # volume, max_deg the ELL fan-in, balance the padding waste
    quality = {
        method: {k: q[k] for k in ("edge_cut", "cut_frac", "balance",
                                   "max_deg")}
        for method, q in (
            (meth, graph.partition_quality(
                g.num_nodes, g.edges,
                graph.partition_graph(g.num_nodes, g.edges, m, seed=0,
                                      method=meth), m))
            for meth in ("bfs_kl", "multilevel"))}
    census = roofline.hlo_census(
        tr._step.lower(tr.state).compile().as_text())
    log = tr.train(epochs)
    print(json.dumps({
        "M": m,
        "platform": jax.devices()[0].platform,
        "partitioner": tr.partitioner,
        "edge_cut_frac": round(tr.partition_stats["cut_frac"], 3),
        "partition_quality": quality,
        "collective_bytes_per_iter": float(census.collective_bytes),
        "per_device_flops": float(census.flops),
        "test_acc": round(float(log.test_acc[-1]), 3),
    }))
""")


def run(dataset: str = "amazon_photo_mini", epochs: int = 25,
        hidden: int = 128, parts=(1, 2, 3, 4, 6),
        partitioner: str = "multilevel") -> list[dict]:
    rows = []
    for m in parts:
        env = cpu_host_env(m)
        out = subprocess.run(
            [sys.executable, "-c", WORKER, dataset, str(m), str(epochs),
             str(hidden), partitioner],
            capture_output=True, text=True, env=env, check=True)
        row = json.loads(out.stdout.strip().splitlines()[-1])
        rows.append(row)
        q = row["partition_quality"]
        print(f"[ablation] M={row['M']} [{row['partitioner']}]: cut "
              f"{row['edge_cut_frac']:.3f} "
              f"(bfs_kl {q['bfs_kl']['edge_cut']} vs multilevel "
              f"{q['multilevel']['edge_cut']}, max_deg "
              f"{q['bfs_kl']['max_deg']} vs {q['multilevel']['max_deg']}) "
              f"coll {row['collective_bytes_per_iter'] / 1e6:.2f} MB/iter "
              f"flops/agent {row['per_device_flops']:.2e} "
              f"test acc {row['test_acc']:.3f}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--partitioner", default="multilevel",
                    choices=["bfs_kl", "multilevel"],
                    help="partition method the trainer uses (quality of "
                         "both methods is reported per M either way)")
    print(json.dumps(run(partitioner=ap.parse_args().partitioner), indent=2))
