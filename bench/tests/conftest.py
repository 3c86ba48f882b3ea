"""Tests of the benchmark harness, run on the CPU at a small size:

    python -m pytest bench/tests
"""
import copy
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# the photo configuration's shape at a size a test run holds
TINY_CONFIG = {
    "name": "tiny-sbm",
    "data": {"nodes": 240, "avg_degree": 8.0, "features": 16, "classes": 4,
             "train": 60, "test": 60, "in_out_ratio": 12.0,
             "generator_seed": 0},
    "model": {"layer_dims": [16, 32, 4], "activation": "relu"},
    "admm": {"nu": 1e-3, "rho": 1e-3, "tau_init": 1.0,
             "backtrack_growth": 2.0, "max_backtracks": 30,
             "fista_iters": 8, "backtrack_rtol": 1e-6},
    "precision": {"dtype": "float32", "matmul": "highest"},
}
TINY_TRAFFIC = {"num_parts": 3, "partitioner": "multilevel",
                "use_kernel": True, "fused": False, "batch_fraction": None}
# run.py's procedure at a test's size; CHECK_STEPS stays as the limits
# were read
TINY_PROCEDURE = {"WARM_ROUNDS": 2, "CHUNK_S": 0.05, "TRACE_SLICE_S": 0.5}


@pytest.fixture(autouse=True)
def tiny_procedure(monkeypatch):
    import run
    for name, value in TINY_PROCEDURE.items():
        monkeypatch.setattr(run, name, value)


def tiny_cell(limits, chips=1, **traffic):
    from harness import spec
    tr = dict(copy.deepcopy(TINY_TRAFFIC), **traffic)
    return spec.Cell(name="tiny", chips=chips,
                     config=copy.deepcopy(TINY_CONFIG), traffic=tr,
                     limits=dict(limits),
                     end_to_end=["round_ms", "setup_s"], per_layer={},
                     units={"round_ms": "ms", "setup_s": "s"})
