"""§Block-sparsity: aggregation cost scales with nnz blocks, not M².

Sweeps community count M on a power-law community graph (Barabási–Albert
inter-community topology: nnz ≈ O(M·attach), dense layout is O(M²)) and
reports, per M:

  * block density nnz/M² and the block-compressed memory ratio;
  * dense einsum vs block-compressed (ELL) aggregation wall time;
  * aggregation FLOPs for the dense reduction (2·M²·n_pad²·C) vs the
    masked/compressed path (2·nnz·n_pad²·C);
  * per-iteration collective bytes of the parallel ADMM trainer's gathers:
    full all-gather vs the neighbour-only volume (messages.gather_bytes) —
    the roofline's collective term, see benchmarks/roofline.py.

Run: PYTHONPATH=src python benchmarks/block_sparsity.py [--quick]
                                                        [--out FILE.json]
Emits machine-readable BENCH_block_sparsity.json next to the repo root.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph, messages
from repro.kernels import ops as kops

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from roofline import collective_terms  # noqa: E402  (benchmarks/roofline.py)


def _timeit(fn, *args, reps: int = 20) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def sweep(ms=(4, 8, 16, 32), nodes_per_part: int = 32, c: int = 64,
          attach: int = 2, seed: int = 0) -> list[dict]:
    rows = []
    for m in ms:
        g, part = graph.synthetic_powerlaw_communities(
            m, nodes_per_part=nodes_per_part, attach=attach, seed=seed,
            feat_dim=c)
        layout = graph.build_community_layout(g.num_nodes, g.edges, part,
                                              compressed=True)
        csr = layout.compress()
        n_pad = layout.n_pad
        nnz, dense_blocks = csr.nnz, m * m

        z = jnp.asarray(layout.pack(
            np.random.default_rng(seed).normal(
                size=(g.num_nodes, c)).astype(np.float32)))
        a = jnp.asarray(layout.a_blocks)
        nbr = jnp.asarray(layout.neighbor_mask)
        ell = (jnp.asarray(csr.ell_blocks), jnp.asarray(csr.ell_indices),
               jnp.asarray(csr.ell_mask))

        dense_fn = jax.jit(lambda a, z: jnp.einsum("mrip,rpc->mic", a, z))
        masked_fn = jax.jit(lambda a, z, nb: kops.community_spmm(a, z, nb))
        ell_fn = jax.jit(kops.community_spmm_ell)

        t_dense = _timeit(dense_fn, a, z)
        t_masked = _timeit(masked_fn, a, z, nbr)
        t_ell = _timeit(ell_fn, *ell, z)

        out_d = dense_fn(a, z)
        np.testing.assert_allclose(np.asarray(ell_fn(*ell, z)),
                                   np.asarray(out_d), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(masked_fn(a, z, nbr)),
                                   np.asarray(out_d), rtol=2e-4, atol=2e-4)

        flops_dense = 2.0 * dense_blocks * n_pad * n_pad * c
        flops_sparse = 2.0 * nnz * n_pad * n_pad * c
        comm = messages.gather_bytes(layout.neighbor_mask, n_pad, [c])
        adj = messages.adjacency_bytes(layout.neighbor_mask, n_pad)
        # scheduled p2p wire volume at one agent per community (the paper's
        # deployment): ppermute rounds move true rows + round padding
        plan = messages.build_neighbor_exchange(layout.neighbor_mask, m,
                                                n_pad)
        wire = messages.exchange_bytes(plan, [c])
        coll = collective_terms(comm["full_bytes"], comm["needed_bytes"],
                                wire["wire_bytes"])
        rows.append({
            "M": m, "n_pad": n_pad, "nnz": nnz,
            "density": nnz / dense_blocks,
            "mem_ratio": csr.blocks.nbytes / layout.a_blocks.nbytes,
            "t_dense_ms": t_dense * 1e3, "t_masked_ms": t_masked * 1e3,
            "t_ell_ms": t_ell * 1e3,
            "gflops_dense": flops_dense / 1e9,
            "gflops_sparse": flops_sparse / 1e9,
            "coll_full_kb": comm["full_bytes"] / 1e3,
            "coll_needed_kb": comm["needed_bytes"] / 1e3,
            "coll_wire_kb": wire["wire_bytes"] / 1e3,
            "coll_padding_kb": wire["padding_bytes"] / 1e3,
            "p2p_rounds": wire["num_rounds"],
            "coll_s_full": coll["collective_s"],
            "coll_s_needed": coll["collective_sparse_s"],
            "coll_s_wire": coll["collective_wire_s"],
            "coll_savings": coll["collective_savings"],
            "coll_wire_savings": coll["collective_wire_savings"],
            "adj_dense_bytes": adj["dense_bytes"],
            "adj_ell_bytes": adj["ell_bytes"],
            "max_deg": adj["max_deg"],
        })
    return rows


def main(quick: bool = False, out: "str | None" = None):
    ms = (4, 8) if quick else (4, 8, 16, 32)
    rows = sweep(ms=ms)
    hdr = (f"{'M':>3s} {'nnz':>4s} {'dens':>5s} {'mem':>5s} "
           f"{'dense_ms':>9s} {'masked_ms':>10s} {'ell_ms':>7s} "
           f"{'GF_dense':>9s} {'GF_nnz':>7s} {'coll_full':>10s} "
           f"{'coll_need':>10s} {'coll_wire':>10s}")
    print(hdr)
    for r in rows:
        print(f"{r['M']:3d} {r['nnz']:4d} {r['density']:5.2f} "
              f"{r['mem_ratio']:5.2f} {r['t_dense_ms']:9.3f} "
              f"{r['t_masked_ms']:10.3f} {r['t_ell_ms']:7.3f} "
              f"{r['gflops_dense']:9.3f} {r['gflops_sparse']:7.3f} "
              f"{r['coll_full_kb']:9.1f}k {r['coll_needed_kb']:9.1f}k "
              f"{r['coll_wire_kb']:9.1f}k")
    big = rows[-1]
    print(f"\nAt M={big['M']}: sparse path does {big['density']:.0%} of the "
          f"dense blocks — FLOPs {big['gflops_sparse']:.3f} vs "
          f"{big['gflops_dense']:.3f} GF, ELL time {big['t_ell_ms']:.3f} vs "
          f"dense {big['t_dense_ms']:.3f} ms, collective "
          f"{big['coll_wire_kb']:.0f}k scheduled p2p wire "
          f"({big['p2p_rounds']} ppermute rounds) vs {big['coll_needed_kb']:.0f}k "
          f"needed vs {big['coll_full_kb']:.0f}k all-gather bytes per round.")
    # the p2p schedule must move no more than the mask-derived need
    assert all(r["coll_wire_kb"] <= r["coll_needed_kb"] for r in rows)
    # nnz grows ~linearly in M on the power-law topology: the sparse-path
    # cost per M must grow far slower than the dense M² path
    m0, m1 = rows[0], rows[-1]
    dense_growth = m1["gflops_dense"] / m0["gflops_dense"]
    sparse_growth = m1["gflops_sparse"] / m0["gflops_sparse"]
    assert sparse_growth < dense_growth, (sparse_growth, dense_growth)
    print(f"FLOP growth {m0['M']}→{m1['M']} communities: dense "
          f"{dense_growth:.1f}×, nnz-proportional {sparse_growth:.1f}×")

    payload = {"quick": quick, "agg_sweep": rows}
    out_path = pathlib.Path(out) if out else \
        pathlib.Path(__file__).resolve().parent.parent / \
        "BENCH_block_sparsity.json"
    out_path.write_text(json.dumps(payload, indent=2))
    print(f"wrote {out_path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small M sweep / few reps (CI smoke)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    main(quick=args.quick, out=args.out)
