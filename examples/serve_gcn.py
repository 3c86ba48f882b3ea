"""Serving example: cached community-block GCN inference under Zipf load.

Trains a small community-partitioned GCN, builds a ``CommunityServer``
over the trained weights, and contrasts three serving modes on the same
heavy-tailed request stream:

  * cached + Zipf-aware admission (the production path),
  * cached + plain LRU admission,
  * cache disabled (every batch recomputes its community's 2-hop chain
    through the packed ELL kernels — the baseline the cache beats).

Then a feature update shows incremental invalidation: only the read
closure of the touched community recomputes; the rest keeps serving out
of cache.

Run:  PYTHONPATH=src python examples/serve_gcn.py
"""
import time

import numpy as np

from repro.core import gcn, graph
from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig
from repro.serve import CommunityServer, ServeConfig, zipf_node_stream

M = 12
BATCH = 64
REQUESTS = 1536


def drive(server, stream):
    n_batches = len(stream) // BATCH
    warmup = max(n_batches // 4, 1)
    times = []
    h0 = t0 = 0
    for i in range(n_batches):
        if i == warmup:
            h0, t0 = server.request_hits, server.request_total
        tic = time.perf_counter()
        server.serve(stream[i * BATCH:(i + 1) * BATCH])
        if i >= warmup:
            times.append(time.perf_counter() - tic)
    ms = np.asarray(times) * 1e3
    hit = (server.request_hits - h0) / max(server.request_total - t0, 1)
    return float(np.percentile(ms, 50)), float(np.percentile(ms, 99)), hit


def main():
    g, part = graph.synthetic_powerlaw_communities(
        M, nodes_per_part=24, attach=2, seed=0, feat_dim=16, size_skew=1.0)
    cfg = gcn.GCNConfig(layer_dims=(16, 32, g.num_classes))
    tr = ParallelADMMTrainer(
        cfg, ADMMConfig(nu=1e-3, rho=1e-3), g, num_parts=M, seed=0,
        part=part, config=TrainerConfig.packed())
    print(f"training M={M} community GCN on N={g.num_nodes}...")
    tr.train(3)
    _, test_acc, _ = tr._metrics(tr.state)
    print(f"test_acc={float(test_acc):.4f}\n")

    stream = zipf_node_stream(g.num_nodes, REQUESTS, s=1.1, seed=1)
    modes = [
        ("zipf-admission cache", ServeConfig(embed_capacity=M + M // 4,
                                             admission="zipf")),
        ("plain-LRU cache     ", ServeConfig(embed_capacity=M + M // 4,
                                             admission="lru")),
        ("cache disabled      ", ServeConfig(cache_enabled=False)),
    ]
    print(f"Zipf(1.1) x {REQUESTS} requests, batch {BATCH}:")
    servers = {}
    for name, scfg in modes:
        srv = CommunityServer.from_trainer(tr, scfg)
        p50, p99, hit = drive(srv, stream)
        servers[name] = srv
        print(f"  {name}  p50 {p50:7.3f} ms  p99 {p99:7.3f} ms  "
              f"hit rate {hit:.3f}")

    # incremental invalidation: touch one node, only its read closure pays
    srv = servers[modes[0][0]]
    node = int(stream[0])
    feats = np.asarray(g.features)[[node]] + 0.1
    rep = srv.update_features([node], feats)
    dirty = [len(c) for c in rep["dirty"]]
    print(f"\nfeature update to node {node} (community "
          f"{int(srv.node_comm[node])}): dirty communities per hop "
          f"{dirty} of {M}, dropped {len(rep['embed'])} embed / "
          f"{len(rep['halo'])} halo entries")
    p50, p99, hit = drive(srv, stream)
    print(f"  post-update           p50 {p50:7.3f} ms  p99 {p99:7.3f} ms  "
          f"hit rate {hit:.3f}  (cache refilled)")


if __name__ == "__main__":
    main()
