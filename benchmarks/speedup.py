"""Paper Table 3: Serial ADMM vs Parallel ADMM wall-time / speedup.

Serial = one community, one device.  Parallel = M=3 multilevel
communities on 3 host devices (the paper used 3 agents on one Xeon; host
CPU devices are real threads, so the speedup mechanism matches), on the
trainer's one path (rows carry the partition's edge_cut / balance /
max_deg).  Each configuration runs in a subprocess so the device count
can differ (XLA locks it at first init).

The paper reports training/communication time separately; a fused XLA
program has no such boundary, so alongside wall-time we report the
*collective byte volume* of the parallel step (the communication the paper
timed) parsed from the compiled HLO, plus the device-resident adjacency
bytes each trainer holds.

Run: PYTHONPATH=src python benchmarks/speedup.py [--quick] [--out FILE.json]
Emits machine-readable BENCH_speedup.json next to the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import textwrap


def cpu_host_env(n_devices: int) -> dict:
    """Environment of a host-mesh worker: pinned to the CPU with
    ``n_devices`` virtual devices, so it never contends for a chip the
    parent may hold, and none of its timings reads as a device number."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    return env


WORKER = textwrap.dedent("""
    import json, sys, time
    import jax
    from repro.core import graph, gcn
    from repro.core.subproblems import ADMMConfig
    mode, dataset, epochs = sys.argv[1], sys.argv[2], int(sys.argv[3])
    hidden = int(sys.argv[4])
    g = graph.synthetic_sbm(dataset, seed=0)
    hyper = 1e-3 if "computers" in dataset else 1e-4
    cfg = gcn.GCNConfig(layer_dims=(g.features.shape[1], hidden,
                                    g.num_classes))
    admm = ADMMConfig(nu=hyper, rho=hyper)
    adjacency_bytes = 0
    if mode == "serial":
        from repro.core.serial import SerialADMMTrainer
        tr = SerialADMMTrainer(cfg, admm, g, seed=0)
        step = tr.step
        adjacency_bytes = int(tr.a_tilde.nbytes)
    else:
        from repro.core.parallel import ParallelADMMTrainer, TrainerConfig
        tr = ParallelADMMTrainer(
            cfg, admm, g, num_parts=3, seed=0,
            config=TrainerConfig(partitioner="multilevel"))
        step = tr.step
        adjacency_bytes = int(tr.data.adjacency_nbytes)
    step(); jax.block_until_ready(tr.state.zs[-1])   # compile
    t0 = time.perf_counter()
    for _ in range(epochs):
        step()
    jax.block_until_ready(tr.state.zs[-1])
    total = time.perf_counter() - t0
    from repro.launch import roofline
    if mode == "serial":
        lowered = tr._step.lower(tr.a_tilde, tr.z0, tr.labels,
                                 tr.train_mask, tr.state)
    else:
        lowered = tr._step.lower(tr.state)
    census = roofline.hlo_census(lowered.compile().as_text())
    acc = tr._metrics(tr.state)
    comm = {}
    if mode != "serial":
        part_q = tr.partition_stats
        comm = {"scheduled_wire_bytes": int(tr.comm_stats["wire_bytes"]),
                "needed_bytes": int(tr.comm_stats["needed_bytes"]),
                "full_bytes": int(tr.comm_stats["full_bytes"]),
                "partitioner": tr.partitioner,
                "edge_cut": int(part_q["edge_cut"]),
                "part_balance": float(part_q["balance"]),
                "part_max_deg": int(part_q["max_deg"])}
    print(json.dumps({"mode": mode, "platform": jax.devices()[0].platform,
                      "total_s": total,
                      "per_epoch_s": total / epochs,
                      "per_device_flops": float(census.flops),
                      "collective_bytes": float(census.collective_bytes),
                      "adjacency_bytes": adjacency_bytes,
                      "test_acc": float(acc[1]), **comm}))
""")


def _run(mode: str, dataset: str, epochs: int, hidden: int) -> dict:
    env = cpu_host_env(1 if mode == "serial" else 3)
    out = subprocess.run(
        [sys.executable, "-c", WORKER, mode, dataset, str(epochs),
         str(hidden)],
        capture_output=True, text=True, env=env, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(epochs: int = 20, hidden: int = 256,
        datasets=("amazon_computers_mini", "amazon_photo_mini")) -> list:
    rows = []
    for ds in datasets:
        serial = _run("serial", ds, epochs, hidden)
        parallel = _run("parallel", ds, epochs, hidden)
        speedup = serial["total_s"] / parallel["total_s"]
        # analytic speedup: per-agent compute ratio from the HLO census —
        # what the wall clock would show on hardware with ≥M real cores
        # (this container has ONE core, so threads serialize; the paper's
        # Xeon had many)
        flops_ratio = (serial["per_device_flops"]
                       / max(parallel["per_device_flops"], 1.0))
        rows.append({
            "mode": "parallel",
            "dataset": ds,
            "serial_total_s": round(serial["total_s"], 3),
            "parallel_total_s": round(parallel["total_s"], 3),
            "serial_per_epoch_s": round(serial["per_epoch_s"], 4),
            "parallel_per_epoch_s": round(parallel["per_epoch_s"], 4),
            "speedup": round(speedup, 2),
            "analytic_compute_speedup": round(flops_ratio, 2),
            "parallel_collective_bytes": parallel["collective_bytes"],
            "scheduled_wire_bytes": parallel.get("scheduled_wire_bytes"),
            "comm_full_bytes": parallel.get("full_bytes"),
            "partitioner": parallel.get("partitioner"),
            "edge_cut": parallel.get("edge_cut"),
            "part_balance": parallel.get("part_balance"),
            "part_max_deg": parallel.get("part_max_deg"),
            "adjacency_bytes": parallel["adjacency_bytes"],
            "serial_adjacency_bytes": serial["adjacency_bytes"],
            "serial_test_acc": round(serial["test_acc"], 3),
            "parallel_test_acc": round(parallel["test_acc"], 3),
        })
        print(f"[speedup] {ds}: serial {serial['total_s']:.2f}s "
              f"parallel {parallel['total_s']:.2f}s -> {speedup:.2f}x "
              f"wall-clock (1 CPU core), {flops_ratio:.2f}x per-agent "
              f"compute, adjacency {parallel['adjacency_bytes']/1e6:.2f} "
              f"MB (paper: 3.30x/2.98x on 3 agents)")
    return rows


def wire_comparison(m: int = 32, hidden: int = 64) -> dict:
    """Analytic transport comparison at M communities, one agent each (the
    paper's deployment, past what this container can host as devices):
    all-gather full volume vs mask-derived need vs the scheduled p2p wire
    (ppermute rounds: true rows + round padding, messages.exchange_bytes).
    """
    from repro.core import graph, messages
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=32, attach=2, seed=0, feat_dim=hidden)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True)
    stats = messages.gather_bytes(layout.neighbor_mask, layout.n_pad,
                                  [hidden])
    plan = messages.build_neighbor_exchange(layout.neighbor_mask, m,
                                            layout.n_pad)
    stats.update(messages.exchange_bytes(plan, [hidden]))
    messages.verify_transport_bytes(stats)
    out = {"M": m,
           "full_bytes": stats["full_bytes"],
           "needed_bytes": stats["needed_bytes"],
           "wire_bytes": stats["wire_bytes"],
           "padding_bytes": stats["padding_bytes"],
           "p2p_rounds": stats["num_rounds"],
           "wire_reduction": round(
               1.0 - stats["wire_bytes"] / stats["full_bytes"], 4)}
    print(f"[speedup] M={m} transport volume/iteration-payload: all-gather "
          f"{out['full_bytes']/1e3:.0f}kB -> p2p wire "
          f"{out['wire_bytes']/1e3:.0f}kB over {out['p2p_rounds']} ppermute "
          f"rounds ({out['wire_reduction']:.0%} reduction)")
    return out


def partition_comparison(m: int = 32, hidden: int = 64) -> dict:
    """Partitioner quality head-to-head on the M=32 power-law benchmark
    graph: bfs_kl (the original stand-in) vs the multilevel
    coarsen→partition→uncoarsen pass (sharding.multilevel).  Per method:
    edge cut (== the cross-community block volume the p2p transport wires),
    balance vs the strict cap, block max_deg (the ELL fan-in every shard
    pays), and the scheduled NeighborExchange wire bytes the partition
    induces at one agent per community.
    """
    from repro.core import graph, messages
    g, _ = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=32, attach=2, seed=0, feat_dim=hidden)
    out = {"M": m, "num_edges": int(g.num_edges), "methods": {}}
    for method in ("bfs_kl", "multilevel"):
        part = graph.partition_graph(g.num_nodes, g.edges, m, seed=0,
                                     method=method)
        q = graph.partition_quality(g.num_nodes, g.edges, part, m)
        layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                              compressed=True)
        plan = messages.build_neighbor_exchange(layout.neighbor_mask, m,
                                                layout.n_pad)
        wire = messages.exchange_bytes(plan, [hidden])
        out["methods"][method] = {
            "edge_cut": q["edge_cut"],
            "cut_frac": round(q["cut_frac"], 4),
            "balance": round(q["balance"], 4),
            "max_deg": q["max_deg"],
            "nnz_blocks": q["nnz_blocks"],
            "n_pad": layout.n_pad,
            "wire_bytes": wire["wire_bytes"],
            "p2p_rounds": wire["num_rounds"],
        }
    kl, ml = out["methods"]["bfs_kl"], out["methods"]["multilevel"]
    print(f"[speedup] M={m} partitioner: bfs_kl cut {kl['edge_cut']} "
          f"(max_deg {kl['max_deg']}, wire {kl['wire_bytes']/1e3:.0f}kB) -> "
          f"multilevel cut {ml['edge_cut']} (max_deg {ml['max_deg']}, wire "
          f"{ml['wire_bytes']/1e3:.0f}kB, "
          f"{1 - ml['edge_cut']/kl['edge_cut']:.0%} fewer cut edges)")
    return out


def ragged_comparison(m: int = 32, hidden: int = 64,
                      size_skew: float = 1.0) -> dict:
    """Size-aware padding head-to-head on the seed-0 size-skewed power-law
    graph at M=32 (Zipf community sizes, large communities on the BA
    periphery — graph.synthetic_powerlaw_communities(size_skew=...)), one
    agent per community.  Per pad mode: the residual-padding accounting
    (messages.pad_stats — pad rows/bytes the payloads carry, pad FLOPs the
    block aggregation spends) and the scheduled NeighborExchange wire —
    whole-n_pad-block messages under ``global``, row-exact payloads over
    size-bucketed sub-rounds under ``bucketed``.  check_bench.py guards
    that bucketed padding undercuts global on every axis and that the
    ragged wire stays at or below the uniform-graph multilevel wire
    (``m32_partition``) — pad waste, not size skew, was the cost.
    """
    import numpy as np
    from repro.core import graph, messages
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=32, attach=2, seed=0, feat_dim=hidden,
        size_skew=size_skew)
    sizes = np.bincount(part, minlength=m)
    out = {"M": m, "size_skew": size_skew,
           "max_size": int(sizes.max()), "min_size": int(sizes.min()),
           "modes": {}}
    for pad_mode in ("global", "bucketed"):
        layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                              compressed=True,
                                              pad_mode=pad_mode)
        plan = messages.build_neighbor_exchange(
            layout.neighbor_mask, m, layout.n_pad,
            sizes=layout.sizes if pad_mode == "bucketed" else None)
        wire = messages.exchange_bytes(plan, [hidden])
        pad = messages.pad_stats(layout.neighbor_mask, layout.sizes,
                                 layout.row_counts, layout.n_pad, [hidden])
        out["modes"][pad_mode] = {
            "n_pad": layout.n_pad,
            "pad_rows": pad["pad_rows"],
            "pad_bytes": pad["pad_bytes"],
            "pad_flops": pad["pad_flops"],
            "pad_flop_frac": round(pad["pad_flop_frac"], 4),
            "wire_bytes": wire["wire_bytes"],
            "true_wire_bytes": wire["p2p_needed_bytes"],
            "p2p_rounds": wire["num_rounds"],
        }
    gl, bu = out["modes"]["global"], out["modes"]["bucketed"]
    print(f"[speedup] M={m} skew={size_skew} ragged padding: global pad "
          f"{gl['pad_bytes']/1e3:.0f}kB/iter-payload "
          f"({100*gl['pad_flop_frac']:.0f}% pad FLOPs), wire "
          f"{gl['wire_bytes']/1e3:.0f}kB -> bucketed pad "
          f"{bu['pad_bytes']/1e3:.0f}kB ({100*bu['pad_flop_frac']:.0f}%), "
          f"row-exact wire {bu['wire_bytes']/1e3:.0f}kB over "
          f"{bu['p2p_rounds']} rounds")
    return out


def packed_comparison(m: int = 32, hidden: int = 64,
                      size_skew: float = 1.0, n_shards: int = 4) -> dict:
    """Packed Σ-bucket-rows resident state vs the strided (M, n_pad, C)
    layout on the seed-0 size-skewed power-law graph at M=32, over a
    ``n_shards`` mesh (k = M/n_shards communities per shard).

    The strided layout prices every resident Z/U/z0 tensor at M·n_pad
    rows — the single largest community pads everyone.  The packed device
    layout (graph.CommunityLayout.device_layout) stores each shard's
    lanes back to back at their bucket row counts, so resident rows drop
    to the shard-max Σ-bucket-rows; check_bench.py guards that the packed
    Z bytes sit strictly below strided here.  The overlap section prices
    the round schedule's *exposed* wire (messages.overlap_stats): what
    the double-buffered per-arrival-group aggregation cannot hide behind
    compute, fed to roofline_terms' overlap-aware collective term.
    """
    import numpy as np
    from repro.core import graph, messages
    from repro.launch.roofline import roofline_terms
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=32, attach=2, seed=0, feat_dim=hidden,
        size_skew=size_skew)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    dl = layout.device_layout(n_shards)
    plan = messages.build_neighbor_exchange(
        layout.neighbor_mask, n_shards, layout.n_pad,
        sizes=layout.sizes, row_counts=layout.eff_row_counts())
    ov = messages.overlap_stats(plan, layout.neighbor_mask, [hidden],
                                enabled=True)
    wire = messages.exchange_bytes(plan, [hidden])
    strided_rows = m * layout.n_pad
    packed_rows = dl.total_rows
    # aggregation FLOPs available to hide the wire: 2·rows·rows·C per
    # stored ELL block pair is what overlap_stats already models; here we
    # price the roofline with the scheduled wire vs its exposed remainder
    terms = roofline_terms(
        flops=ov["hidden_wire_s"] * float(ov["model"]["peak_flops"]),
        hbm_bytes=packed_rows * hidden * 4,
        collective_total=wire["wire_bytes"],
        exposed_collective=ov["exposed_wire_bytes"])
    out = {
        "M": m, "n_shards": n_shards, "size_skew": size_skew,
        "n_pad": layout.n_pad,
        "strided_rows": int(strided_rows),
        "packed_rows": int(packed_rows),
        "bucket_rows": int(dl.true_rows),
        "node_rows": int(np.asarray(layout.sizes).sum()),
        "strided_z_bytes": int(strided_rows * hidden * 4),
        "packed_z_bytes": int(packed_rows * hidden * 4),
        "resident_reduction": round(1.0 - packed_rows / strided_rows, 4),
        "wire_bytes": int(wire["wire_bytes"]),
        "p2p_rounds": int(wire["num_rounds"]),
        "overlap": {
            "num_rounds": int(ov["num_rounds"]),
            "num_groups": int(ov["num_groups"]),
            "overlap_efficiency": float(ov["overlap_efficiency"]),
            "total_wire_s": float(ov["total_wire_s"]),
            "exposed_wire_s": float(ov["exposed_wire_s"]),
            "exposed_wire_bytes": int(ov["exposed_wire_bytes"]),
        },
        "roofline": {k: (float(v) if not isinstance(v, str) else v)
                     for k, v in terms.items()},
    }
    print(f"[speedup] M={m} skew={size_skew} packed state over {n_shards} "
          f"shards: strided {out['strided_z_bytes']/1e3:.0f}kB resident Z "
          f"-> packed {out['packed_z_bytes']/1e3:.0f}kB "
          f"({out['resident_reduction']:.0%} down, Σ-bucket floor "
          f"{out['bucket_rows']} rows); overlap hides "
          f"{100*out['overlap']['overlap_efficiency']:.2f}% of "
          f"{out['wire_bytes']/1e3:.0f}kB wire over "
          f"{out['overlap']['num_rounds']} rounds")
    return out


MB_WORKER = textwrap.dedent("""
    import json, sys
    import numpy as np, jax
    from repro.core import graph, gcn
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig, AXIS
    from repro.core.subproblems import ADMMConfig
    from jax.sharding import AxisType
    m, hidden, epochs = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    frac = float(sys.argv[4])
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=12, attach=1, seed=0, feat_dim=hidden,
        size_skew=1.0)
    cfg = gcn.GCNConfig(layer_dims=(hidden, hidden,
                                    int(np.asarray(g.labels).max()) + 1))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:4])
    out = {}
    for name, cfg_t in (("full", TrainerConfig.packed()),
                        ("minibatch",
                         TrainerConfig.minibatch(batch_fraction=frac))):
        tr = ParallelADMMTrainer(cfg, admm, g, num_parts=m, seed=0,
                                 part=part, mesh=mesh, config=cfg_t)
        lag0 = float(tr._lagrangian(tr.state))
        for _ in range(epochs):
            tr.step()
        out[name] = {"lagrangian_0": lag0,
                     "lagrangian": float(tr._lagrangian(tr.state)),
                     "minibatch": {k: v for k, v in
                                   tr.comm_stats["minibatch"].items()}}
    out["platform"] = jax.devices()[0].platform
    print(json.dumps(out))
""")


def minibatch_comparison(m: int = 32, hidden: int = 64,
                         size_skew: float = 1.0, n_shards: int = 4,
                         batch_fraction: float = 0.25,
                         epochs: int = 10) -> dict:
    """Stochastic community minibatching on the seed-0 size-skewed M=32
    power-law graph over a 4-shard mesh.

    Analytic half: the batch sampler's cycle-0 schedule
    (sharding.partition.CommunityBatchSampler, Σ-bucket-rows balanced)
    prices every sampled round's restricted exchange
    (messages.restrict_exchange — only messages *into* sampled shards
    survive) and the sampled resident sweep rows, against the full-batch
    plan.  check_bench.py guards both drop ≥2× and that the wire ratio
    stays ≤ batch_fraction + slack (round padding is the only excess).

    Measured half: a 4-host-device subprocess trains the full-batch
    packed trainer and the ``batch_fraction`` minibatch trainer for the
    same ``epochs`` rounds and reports both augmented Lagrangians — the
    staleness-decayed penalty (docs/minibatch.md) must keep the sampled
    run's final Lagrangian within the pinned gap of full batch.
    """
    import numpy as np
    from repro.core import graph, messages
    from repro.sharding.partition import CommunityBatchSampler
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=32, attach=2, seed=0, feat_dim=hidden,
        size_skew=size_skew)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    plan = messages.build_neighbor_exchange(
        layout.neighbor_mask, n_shards, layout.n_pad,
        sizes=layout.sizes, row_counts=layout.eff_row_counts())
    full_wire = int(messages.exchange_bytes(plan, [hidden])["wire_bytes"])
    rc = np.asarray(layout.eff_row_counts(),
                    dtype=np.int64).reshape(n_shards, -1)
    shard_rows = rc.sum(axis=1)
    sampler = CommunityBatchSampler(n_shards, batch_fraction, seed=0,
                                    weights=shard_rows.astype(np.float64))
    wires, rows = [], []
    for b in sampler.cycle(0):
        sub = plan if len(b) == n_shards else \
            messages.restrict_exchange(plan, frozenset(b))
        wires.append(int(messages.exchange_bytes(
            sub, [hidden])["wire_bytes"]))
        rows.append(int(shard_rows[list(b)].sum()))

    env = cpu_host_env(4)
    proc = subprocess.run(
        [sys.executable, "-c", MB_WORKER, str(m), "16", str(epochs),
         str(batch_fraction)],
        capture_output=True, text=True, env=env, check=True)
    run = json.loads(proc.stdout.strip().splitlines()[-1])

    out = {
        "M": m, "n_shards": n_shards, "size_skew": size_skew,
        "batch_fraction": batch_fraction,
        "num_batches": int(sampler.num_batches),
        "schedule": [list(b) for b in sampler.cycle(0)],
        "full_wire_bytes": full_wire,
        "sampled_wire_bytes": wires,
        "mean_sampled_wire_bytes": float(np.mean(wires)),
        "wire_ratio": round(float(np.mean(wires)) / full_wire, 4),
        "full_state_rows": int(shard_rows.sum()),
        "sampled_state_rows": rows,
        "mean_sampled_state_rows": float(np.mean(rows)),
        "state_ratio": round(float(np.mean(rows)) / float(shard_rows.sum()),
                             4),
        "epochs": epochs,
        "lagrangian_full": run["full"]["lagrangian"],
        "lagrangian_minibatch": run["minibatch"]["lagrangian"],
        "lagrangian_0": run["full"]["lagrangian_0"],
        "lagrangian_gap": round(
            (run["minibatch"]["lagrangian"] - run["full"]["lagrangian"])
            / max(abs(run["full"]["lagrangian"]), 1e-9), 4),
    }
    print(f"[speedup] M={m} skew={size_skew} minibatch f={batch_fraction}: "
          f"wire {full_wire/1e3:.0f}kB -> mean sampled "
          f"{out['mean_sampled_wire_bytes']/1e3:.0f}kB "
          f"({out['wire_ratio']:.0%}), sweep rows "
          f"{out['full_state_rows']} -> {out['mean_sampled_state_rows']:.0f} "
          f"({out['state_ratio']:.0%}); Lagrangian after {epochs} rounds "
          f"full {out['lagrangian_full']:.4f} vs sampled "
          f"{out['lagrangian_minibatch']:.4f} "
          f"(gap {out['lagrangian_gap']:+.1%})")
    return out


FU_WORKER = textwrap.dedent("""
    import json, sys
    import numpy as np, jax
    import jax.numpy as jnp
    from repro.core import graph, gcn
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig, AXIS
    from repro.core.subproblems import ADMMConfig
    from jax.sharding import AxisType
    from repro.analysis.rules.memory import fused_agg_handoffs
    m, hidden, epochs = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=12, attach=1, seed=0, feat_dim=hidden,
        size_skew=1.0)
    cfg = gcn.GCNConfig(layer_dims=(hidden, hidden,
                                    int(np.asarray(g.labels).max()) + 1))
    admm = ADMMConfig(nu=1e-3, rho=1e-3)
    mesh = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,), devices=jax.devices()[:4])
    out = {"num_layers": cfg.num_layers}
    trs = {}
    for name, fused in (("unfused", False), ("fused", True)):
        tr = ParallelADMMTrainer(
            cfg, admm, g, num_parts=m, seed=0, part=part, mesh=mesh,
            config=TrainerConfig.packed(fused=fused))
        jx = jax.make_jaxpr(tr._step)(tr.state)
        out[name + "_handoffs"] = len(fused_agg_handoffs(jx,
                                                         tr.layout.n_pad))
        trs[name] = tr
    def delta(a, b):
        return max(
            max(float(jnp.max(jnp.abs(x - y)))
                for x, y in zip(a.weights, b.weights)),
            max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a.zs, b.zs)),
            float(jnp.max(jnp.abs(a.u - b.u))))
    # per-iteration parity from a shared input state: the backtracking
    # line searches branch on loss comparisons, so across iterations a
    # dot-order epsilon can flip a step count and the trajectories
    # diverge discretely — parity is pinned per step, not per trajectory
    # (copies because the step jit donates its input buffers)
    state = trs["unfused"].state
    deltas = []
    for _ in range(epochs):
        fused_next = trs["fused"]._step(jax.tree.map(jnp.copy, state))
        state = trs["unfused"]._step(state)
        deltas.append(delta(state, fused_next))
    out["parity_max_delta"] = max(deltas)
    out["lagrangian_unfused"] = float(trs["unfused"]._lagrangian(state))
    out["lagrangian_fused"] = float(trs["fused"]._lagrangian(fused_next))
    out["platform"] = jax.devices()[0].platform
    print(json.dumps(out))
""")


def fused_comparison(m: int = 32, hidden: int = 64,
                     size_skew: float = 1.0, n_shards: int = 4,
                     epochs: int = 3) -> dict:
    """Fused aggregation→Z-update kernel vs the two-step packed path on
    the seed-0 power-law graph at M=32 over a 4-shard mesh.

    Analytic half: per shard per iteration, every Z-update
    aggregation→GEMM site unfused writes its aggregated (k, n_pad, C_in)
    stack to HBM and reads it back for the GEMM — the fused kernel keeps
    it in VMEM scratch, so its HBM intermediate traffic is zero
    (roofline.fused_agg_traffic prices both).  Measured half: a
    4-host-device subprocess steps the fused and unfused packed trainers
    from a shared state each round and reports the max per-iteration
    W/Z/U divergence (the fused GEMM reassociates (A·Z)·W to A·(Z·W) —
    dot-order tolerance, pinned at 1e-6 by check_bench.py; the
    line-search branches make *trajectory* divergence discrete, so
    parity is per step) plus the traced jaxpr's
    aggregation→dot handoff counts (the memory/fused-no-intermediate
    dataflow walk): the fused step must sit at the W-update floor of one
    per layer, strictly below the unfused step.
    """
    from repro.core import graph
    from repro.launch.roofline import fused_agg_traffic
    g, part = graph.synthetic_powerlaw_communities(
        m, nodes_per_part=32, attach=2, seed=0, feat_dim=hidden,
        size_skew=size_skew)
    layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                          compressed=True,
                                          pad_mode="bucketed")
    num_classes = g.num_classes
    dims = [hidden, hidden, num_classes]
    L = len(dims) - 1
    # the fused Z-update sites per iteration: target1 (hidden layers),
    # q (hidden layers), and the Z_L target b evaluated twice by the
    # penultimate refresh (b, b_new)
    sites = [(dims[l - 1], dims[l]) for l in range(1, L)] \
        + [(dims[l], dims[l + 1]) for l in range(1, L)] \
        + [(dims[L - 1], dims[L])] * 2
    traffic = fused_agg_traffic((m // n_shards) * layout.n_pad, sites)

    env = cpu_host_env(4)
    proc = subprocess.run(
        [sys.executable, "-c", FU_WORKER, str(m), "16", str(epochs)],
        capture_output=True, text=True, env=env, check=True)
    run = json.loads(proc.stdout.strip().splitlines()[-1])

    out = {
        "M": m, "n_shards": n_shards, "hidden": hidden,
        "n_pad": int(layout.n_pad),
        "num_layers": int(run["num_layers"]),
        **traffic,
        "traffic_reduction": round(
            1.0 - traffic["fused_intermediate_bytes"]
            / max(traffic["unfused_intermediate_bytes"], 1), 4),
        "epochs": epochs,
        "parity_max_delta": float(run["parity_max_delta"]),
        "parity_tol": 1e-6,
        "fused_handoffs": int(run["fused_handoffs"]),
        "unfused_handoffs": int(run["unfused_handoffs"]),
        "lagrangian_fused": run["lagrangian_fused"],
        "lagrangian_unfused": run["lagrangian_unfused"],
    }
    print(f"[speedup] M={m} fused agg→GEMM over {n_shards} shards: "
          f"intermediate HBM "
          f"{out['unfused_intermediate_bytes']/1e3:.0f}kB/shard/iter -> "
          f"{out['fused_intermediate_bytes']}B "
          f"({out['traffic_reduction']:.0%} down, {out['sites']} sites); "
          f"agg→dot handoffs {out['unfused_handoffs']} -> "
          f"{out['fused_handoffs']}; parity after {epochs} rounds "
          f"{out['parity_max_delta']:.2e} (tol {out['parity_tol']:.0e})")
    return out


def main(quick: bool = False, out: "str | None" = None):
    if quick:
        rows = run(epochs=2, hidden=32, datasets=("amazon_photo_mini",))
    else:
        rows = run()
    payload = {"quick": quick, "rows": rows, "m32_wire": wire_comparison(),
               "m32_partition": partition_comparison(),
               "m32_ragged": ragged_comparison(),
               "m32_packed": packed_comparison(),
               "m32_minibatch": minibatch_comparison(),
               "m32_fused": fused_comparison()}
    out_path = pathlib.Path(out) if out else \
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_speedup.json"
    out_path.write_text(json.dumps(payload, indent=2))
    print(f"wrote {out_path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny run (CI smoke): 1 dataset, 2 epochs")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    print(json.dumps(main(quick=args.quick, out=args.out)["rows"], indent=2))
