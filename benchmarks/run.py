"""Benchmark orchestrator — one entry per paper table/figure + the
beyond-paper additions.  Prints ``name,value,derived`` CSV lines and writes
results/bench/*.json.

  table3_speedup    paper Table 3 (serial vs parallel ADMM wall time)
  fig2_accuracy     paper Figure 2 (ADMM vs SGD-family optimizers)
  roofline          §Roofline terms per (arch × shape), from the dry-run
  layerwise         beyond-paper: blockwise ADMM on a transformer
  kernels           per-kernel micro-latency (oracle path on CPU)

Run everything: ``PYTHONPATH=src python -m benchmarks.run``
Subset:         ``... -m benchmarks.run --only table3_speedup,roofline``
CI smoke:       ``... benchmarks/run.py --quick`` — emits the repo-root
``BENCH_block_sparsity.json`` / ``BENCH_speedup.json`` / ``BENCH_serving.json``
quick payloads and validates them with benchmarks/check_bench.py (schema +
the p2p-vs-allgather wire-byte and serving hit-rate/latency regression
guards).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:       # allow `python benchmarks/run.py`
    sys.path.insert(0, str(REPO_ROOT))

OUT_DIR = REPO_ROOT / "results" / "bench"


def bench_table3_speedup() -> list[tuple[str, float, str]]:
    from benchmarks import speedup
    rows = speedup.run(epochs=15, hidden=256)
    out = []
    for r in rows:
        tag = f"table3/{r['dataset']}/{r['mode']}"
        out.append((f"{tag}/serial_s", r["serial_total_s"], ""))
        out.append((f"{tag}/parallel_s", r["parallel_total_s"], ""))
        out.append((f"{tag}/speedup", r["speedup"],
                    "paper: 3.30x (Computers); 2.98x (Photo)"))
        out.append((f"{tag}/adjacency_mb",
                    round(r["adjacency_bytes"] / 1e6, 3), ""))
    (OUT_DIR / "table3_speedup.json").write_text(json.dumps(rows, indent=2))
    return out


def bench_fig2_accuracy() -> list[tuple[str, float, str]]:
    from benchmarks import accuracy
    res = accuracy.run(dataset="amazon_photo_mini", epochs=40, hidden=256)
    out = []
    for name, curve in res["curves"].items():
        out.append((f"fig2/{res['dataset']}/{name}/final_test_acc",
                    round(curve["test"][-1], 4), ""))
    (OUT_DIR / "fig2_accuracy.json").write_text(json.dumps(res, indent=2))
    return out


def bench_roofline() -> list[tuple[str, float, str]]:
    from benchmarks import roofline
    rows = roofline.run()
    out = []
    for r in rows:
        key = f"roofline/{r['arch']}/{r['shape']}"
        out.append((f"{key}/dominant_term_s",
                    max(r["compute_s"], r["memory_lo_s"],
                        r["collective_s"]), r["dominant"]))
    (OUT_DIR / "roofline.json").write_text(json.dumps(rows, indent=2))
    return out


def bench_layerwise() -> list[tuple[str, float, str]]:
    from benchmarks import layerwise_bench
    res = layerwise_bench.run(arch="qwen2-7b", iters=6)
    (OUT_DIR / "layerwise.json").write_text(json.dumps(res, indent=2))
    return [("layerwise/qwen2-7b/admm_ce", res["admm_ce"],
             f"adam_ce={res['adam_ce']:.4f} same wall-time"),
            ("layerwise/qwen2-7b/residual", res["admm_residual"], "")]


def bench_kernels() -> list[tuple[str, float, str]]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    rng = np.random.default_rng(0)
    out = []

    def timeit(fn, *args, n=5):
        r = fn(*args)
        jax.block_until_ready(r[0] if isinstance(r, tuple) else r)
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn(*args)
            jax.block_until_ready(r[0] if isinstance(r, tuple) else r)
        return (time.perf_counter() - t0) / n * 1e6

    a = jnp.asarray(rng.normal(size=(3, 256, 256)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(3, 256, 128)).astype(np.float32))
    mask = jnp.asarray([True, True, False])
    us = timeit(jax.jit(lambda a, z: ref.community_spmm_ref(a, z, mask)),
                a, z)
    out.append(("kernels/community_spmm_ref_us", round(us, 1),
                "jnp oracle on CPU; pallas path targets TPU"))

    q = jnp.asarray(rng.normal(size=(1, 512, 4, 64)).astype(np.float32))
    us = timeit(jax.jit(lambda q: ref.flash_attention_ref(q, q, q)), q)
    out.append(("kernels/flash_attention_ref_us", round(us, 1), ""))

    x = jnp.asarray(rng.normal(size=(2, 256, 4, 32)).astype(np.float32))
    dt = jnp.abs(jnp.asarray(rng.normal(size=(2, 256, 4)).astype(np.float32)))
    av = -jnp.abs(jnp.asarray(rng.normal(size=(4,)).astype(np.float32)))
    bm = jnp.asarray(rng.normal(size=(2, 256, 1, 32)).astype(np.float32))
    us = timeit(jax.jit(lambda x, dt: ref.ssd_scan_ref(x, dt, av, bm, bm,
                                                       chunk=64)), x, dt)
    out.append(("kernels/ssd_scan_ref_us", round(us, 1), ""))
    return out


def bench_dryrun_summary() -> list[tuple[str, float, str]]:
    from benchmarks import dryrun_summary
    rows = dryrun_summary.run()
    (OUT_DIR / "dryrun_summary.json").write_text(json.dumps(rows, indent=2))
    n_fit = sum(r["fits"] for r in rows)
    return [("dryrun/combinations_fitting_hbm", n_fit,
             f"of {len(rows)} lowered+compiled")]


def bench_perf_report() -> list[tuple[str, float, str]]:
    from benchmarks import perf_report
    rows = perf_report.run()
    (OUT_DIR / "perf_report.json").write_text(json.dumps(rows, indent=2))
    return [(f"perf/{r['pair']}/collective_speedup",
             r["speedup_collective"], "") for r in rows]


def bench_ablation() -> list[tuple[str, float, str]]:
    from benchmarks import ablation_communities
    rows = ablation_communities.run(epochs=15, parts=(1, 3, 6))
    (OUT_DIR / "ablation_communities.json").write_text(
        json.dumps(rows, indent=2))
    return [(f"ablation/M={r['M']}/test_acc", r["test_acc"],
             f"cut={r['edge_cut_frac']}") for r in rows]


BENCHES = {
    "table3_speedup": bench_table3_speedup,
    "fig2_accuracy": bench_fig2_accuracy,
    "roofline": bench_roofline,
    "dryrun_summary": bench_dryrun_summary,
    "perf_report": bench_perf_report,
    "layerwise": bench_layerwise,
    "ablation": bench_ablation,
    "kernels": bench_kernels,
}


def quick() -> None:
    """CI smoke: quick BENCH_*.json emission + schema/regression checks."""
    from benchmarks import block_sparsity, check_bench, serving, speedup
    block_sparsity.main(quick=True)
    speedup.main(quick=True)
    serving.main(quick=True)
    check_bench.main()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benches")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: quick BENCH_*.json + check_bench")
    args = ap.parse_args()
    if args.quick:
        if args.only:
            ap.error("--quick runs a fixed smoke set; drop --only or run "
                     "the subset without --quick")
        quick()
        return
    names = args.only.split(",") if args.only else list(BENCHES)
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    print("name,value,derived")
    for name in names:
        rows = BENCHES[name]()
        for key, value, derived in rows:
            print(f"{key},{value},{derived}")


if __name__ == "__main__":
    main()
