"""End-to-end driver: community-parallel ADMM GCN training (the paper's
Parallel ADMM) for a few hundred epochs, with partition diagnostics,
checkpointing and the bf16-message option.

Run with multiple agents (each community on its own host device):
  XLA_FLAGS=--xla_force_host_platform_device_count=3 \\
  PYTHONPATH=src python examples/train_gcn_communities.py --parts 3 \\
      --epochs 200 --comm-bf16
"""
import argparse

import numpy as np

from repro import checkpoint as ckpt
from repro.core import gcn, graph
from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
from repro.core.subproblems import ADMMConfig
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="amazon_photo_mini",
                    choices=list(graph.DATASET_STATS))
    ap.add_argument("--parts", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=200)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--comm-bf16", action="store_true",
                    help="bf16 message payloads (§Perf optimization)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route aggregation through the Pallas kernels "
                         "(native on TPU; interpret mode elsewhere only "
                         "with REPRO_PALLAS_INTERPRET=1)")
    ap.add_argument("--partitioner", default="multilevel",
                    choices=["bfs_kl", "multilevel"],
                    help="community detection: multilevel coarsen→partition"
                         "→uncoarsen (METIS scheme, sharding.multilevel — "
                         "lower edge cut, hence less p2p wire) or the "
                         "BFS-grow + Kernighan-Lin stand-in (bfs_kl)")
    ap.add_argument("--adjacency-bf16", action="store_true",
                    help="store the ELL adjacency blocks in bf16 (half the "
                         "resident bytes; aggregation still accumulates "
                         "f32)")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffer the p2p rounds against the ELL "
                         "aggregation")
    ap.add_argument("--fused", action="store_true",
                    help="fuse the packed ELL aggregation with the "
                         "Z-update GEMM in one Pallas pass (docs/layout.md "
                         "§5); the aggregated intermediate never touches "
                         "HBM")
    ap.add_argument("--batch-fraction", type=float, default=None,
                    help="stochastic community minibatching: sample this "
                         "fraction of shards per ADMM round (seeded, "
                         "balance-aware batches; docs/minibatch.md); 1.0 "
                         "is bitwise full-batch")
    ap.add_argument("--stale-decay", type=float, default=0.5,
                    help="per-round decay of unsampled communities' "
                         "consensus penalty weight (d_r = decay^age)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="seed of the community batch sampler")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    g = graph.synthetic_sbm(args.dataset, seed=0)
    hyper = 1e-3 if "computers" in args.dataset else 1e-4
    cfg = gcn.GCNConfig(layer_dims=(g.features.shape[1], args.hidden,
                                    g.num_classes))
    admm = ADMMConfig(nu=hyper, rho=hyper)

    part = graph.partition_graph(g.num_nodes, g.edges, args.parts, seed=0,
                                 method=args.partitioner)
    q = graph.partition_quality(g.num_nodes, g.edges, part, args.parts)
    print(f"partition [{args.partitioner}]: {args.parts} communities, sizes "
          f"{np.bincount(part).tolist()}, edge cut "
          f"{q['edge_cut']}/{g.num_edges} ({100 * q['cut_frac']:.1f}%), "
          f"balance {q['balance']:.3f}, block max_deg {q['max_deg']}")

    # every option flag above maps 1:1 onto a TrainerConfig field by its
    # argparse dest — the config does the validation
    trainer = ParallelADMMTrainer(cfg, admm, g, num_parts=args.parts,
                                  seed=0, part=part,
                                  config=TrainerConfig.from_cli_args(args))
    print(f"mesh: {dict(trainer.mesh.shape)}; neighbour topology:\n"
          f"{np.asarray(trainer.layout.neighbor_mask).astype(int)}")
    cs = trainer.comm_stats
    print(f"collective/iter [p2p]: full "
          f"{cs['full_bytes'] / 1e6:.2f} MB, neighbour-only "
          f"{cs['needed_bytes'] / 1e6:.2f} MB "
          f"({cs['nnz_blocks']}/{cs['dense_blocks']} blocks, "
          f"{100 * cs['savings_ratio']:.0f}% saved), scheduled wire "
          f"{cs['wire_bytes'] / 1e6:.2f} MB")
    sizes = trainer.layout.sizes
    print(f"padding [bucketed]: community sizes "
          f"{int(sizes.min())}..{int(sizes.max())} padded to per-size "
          f"buckets of n_pad={trainer.layout.n_pad}; residual pad rows "
          f"{cs['pad_rows']} -> {cs['pad_bytes'] / 1e3:.1f} kB payload "
          f"padding and {cs['pad_flops'] / 1e6:.1f} MFLOP "
          f"({100 * cs['pad_flop_frac']:.1f}%) pad work per iteration")
    adj = cs["adjacency"]
    mode = "ELL, bf16 blocks" if args.adjacency_bf16 else "ELL"
    print(f"adjacency on device [{mode}]: {adj['resident_bytes'] / 1e6:.2f} "
          f"MB (dense would be {adj['dense_bytes'] / 1e6:.2f} MB, "
          f"max_deg {adj['max_deg']})")
    st = cs["state"]
    print(f"resident state [packed]: "
          f"{st['rows']} rows / {st['resident_bytes'] / 1e6:.2f} MB "
          f"(strided {st['strided_rows']} rows / "
          f"{st['strided_equiv_bytes'] / 1e6:.2f} MB, Σ-bucket floor "
          f"{st['bucket_rows']} rows)")
    if cs["overlap"]["enabled"]:
        ov = cs["overlap"]
        print(f"overlap: {100 * ov['overlap_efficiency']:.2f}% of "
              f"{cs['wire_bytes'] / 1e6:.2f} MB wire hidden across "
              f"{ov['num_groups']} arrival groups "
              f"({ov['num_rounds']} rounds)")
    if cs["minibatch"]["enabled"]:
        mb = cs["minibatch"]
        print(f"minibatch [f={mb['batch_fraction']}, decay="
              f"{mb['stale_decay']}]: {mb['num_batches']} batches/cycle "
              f"{mb['schedule']}, wire {mb['full_wire_bytes'] / 1e6:.2f} MB "
              f"-> mean sampled {mb['mean_sampled_wire_bytes'] / 1e6:.2f} "
              f"MB, sweep rows {mb['full_state_rows']} -> mean "
              f"{mb['mean_sampled_state_rows']:.0f}")

    log = trainer.train(args.epochs, verbose=False)
    stride = max(1, args.epochs // 10)
    for i in range(0, len(log.epoch), stride):
        print(f"epoch {log.epoch[i]:4d} train {log.train_acc[i]:.3f} "
              f"test {log.test_acc[i]:.3f} residual {log.residual[i]:.2e}")
    print(f"final: train {log.train_acc[-1]:.3f} test {log.test_acc[-1]:.3f}")

    if args.ckpt_dir:
        path = ckpt.save(args.ckpt_dir,
                         {"weights": list(trainer.state.weights)},
                         step=args.epochs)
        print(f"checkpoint -> {path}")


if __name__ == "__main__":
    main()
