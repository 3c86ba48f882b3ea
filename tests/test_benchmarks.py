"""CI smoke for the benchmark JSON emitters: --quick runs must produce
machine-readable BENCH_*.json payloads with the (mode, M, bytes,
per-epoch seconds) fields the perf trajectory tracking consumes."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)   # benchmarks/ namespace package

from benchmarks import check_bench  # noqa: E402


def _run_bench(script: str, out_path: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", script),
         "--quick", "--out", out_path],
        capture_output=True, text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    with open(out_path) as fh:
        return json.load(fh)


def test_block_sparsity_quick_json(tmp_path):
    payload = _run_bench("block_sparsity.py",
                         str(tmp_path / "BENCH_block_sparsity.json"))
    assert payload["quick"] is True
    assert payload["agg_sweep"]
    # check_bench enforces the wire ≤ needed ≤ full chain per row
    check_bench.check_block_sparsity(payload)
    # the ELL store tracks nnz blocks: at the largest M of the sweep it is
    # strictly smaller than the dense block tensor would be
    top = payload["agg_sweep"][-1]
    assert top["adj_ell_bytes"] < top["adj_dense_bytes"], top


@pytest.mark.slow
def test_speedup_quick_json(tmp_path):
    payload = _run_bench("speedup.py", str(tmp_path / "BENCH_speedup.json"))
    assert payload["quick"] is True
    check_bench.check_speedup(payload)
    assert {r["mode"] for r in payload["rows"]} == {"parallel"}
    # the p2p transport's wire-byte win at M=32 (acceptance criterion)
    assert payload["m32_wire"]["wire_bytes"] < payload["m32_wire"]["full_bytes"]
    # the multilevel partitioner's cut win at M=32 (acceptance criterion):
    # strictly fewer cut edges, no worse ELL fan-in, no more wire
    mp = payload["m32_partition"]["methods"]
    assert mp["multilevel"]["edge_cut"] < mp["bfs_kl"]["edge_cut"]
    assert mp["multilevel"]["max_deg"] <= mp["bfs_kl"]["max_deg"]
    assert mp["multilevel"]["wire_bytes"] <= mp["bfs_kl"]["wire_bytes"]
    for r in payload["rows"]:
        assert {"mode", "dataset", "adjacency_bytes",
                "parallel_per_epoch_s", "serial_per_epoch_s"} <= set(r)
        assert r["parallel_per_epoch_s"] > 0
