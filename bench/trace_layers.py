"""A cell's ADMM round split by layer: the device time under each layer's
scope of the W and Z updates, and each line search's counters.

    python3 bench/trace_layers.py --workload <cell> --seed <n> \
        [--rounds 20] [--save-trace out.json.gz]

The rounds are ``trace_program.trace_cell``'s: its set-up, warm rounds and
profiled rounds, whose result is printed whole under ``program``.  The
same trace is then reduced by the program's per-layer scopes, ``admm_w/l1``
… ``admm_w/lL`` and ``admm_z/l1`` … ``admm_z/l{L-1}``, and by the exchange's
``admm_exchange``: device ms per round of the ops under each, a ``while``
and its body counted once, the mean over devices.  ``split_sum`` gives, per
sub-update, the sum of its layers beside the unsplit reader
(``w_update_ms``, ``z_update_ms``).  ``searches`` gives each line search's
objective evaluations and capped searches per round, the mean over shards,
from the per-search columns of ``state.probes`` (``probe_columns`` of
``repro.core.parallel``); it is empty where the counter has only its two
summed columns.  Without the cell's chips the tool exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

EXCHANGE_SCOPE = "admm_exchange"
SPLIT = {"admm_w": "w_update_ms", "admm_z": "z_update_ms"}


def in_scope_path(path: str, scope: str) -> bool:
    """Whether the components of ``scope`` (``admm_w/l2``) appear in
    ``path`` one after the other."""
    parts, want = path.split("/"), scope.split("/")
    return any(parts[i:i + len(want)] == want
               for i in range(len(parts) - len(want) + 1))


def scope_ms_per_round(tr: dict, rounds: int, scope: str) -> float | None:
    """Device ms per round of the ops traced under ``scope``, the mean over
    devices; None when no op is."""
    from harness import trace
    devs = tr["devices"]
    if rounds <= 0 or not devs or \
            not all("scoped" in d for d in devs.values()):
        return None
    if not any(in_scope_path(e[0], scope) for d in devs.values()
               for e in d["scoped"]):
        return None
    t0, t1 = trace.window_ns(tr)

    def dev_s(dev):
        evs = [e for e in devs[dev]["scoped"] if in_scope_path(e[0], scope)]
        return trace.total(trace.merge(trace.clip(evs, t0, t1))) / 1e9
    return 1e3 * trace.mean_over_devices(tr, dev_s) / rounds


def layer_scopes(num_layers: int) -> list[str]:
    return ([f"admm_w/l{l}" for l in range(1, num_layers + 1)]
            + [f"admm_z/l{l}" for l in range(1, num_layers)])


def search_counts(counters: dict, columns: list[str]) -> dict:
    """Per search, its evaluations and capped searches per round, the mean
    over shards; {} when the counter lacks the per-search columns."""
    import numpy as np
    before = np.asarray(counters["before"], float)
    after = np.asarray(counters["after"], float)
    if counters["rounds"] <= 0 or after.shape[1] != len(columns):
        return {}
    per = (after - before).mean(axis=0) / counters["rounds"]
    out: dict = {}
    for name, v in zip(columns[2:], per[2:]):
        search, kind = name.split(".")
        out.setdefault(search, {})[kind] = float(v)
    return out


def split(tr: dict, rounds: int, num_layers: int, metrics: dict) -> dict:
    layers = {s: scope_ms_per_round(tr, rounds, s)
              for s in layer_scopes(num_layers) + [EXCHANGE_SCOPE]}
    sums = {}
    for outer, reader in SPLIT.items():
        parts = [v for k, v in layers.items()
                 if k.startswith(outer + "/") and v is not None]
        if parts and reader in metrics:
            sums[outer] = {"layers": sum(parts), reader: metrics[reader]}
    return {"layers": {k: v for k, v in layers.items() if v is not None},
            "split_sum": sums}


def trace_layers(cell, seed: int, rounds: int, devices,
                 save_trace: str | None = None) -> dict:
    import trace_program
    from harness import setup, trace
    setup.use_program()
    from repro.core import parallel

    num_layers = len(cell.config["model"]["layer_dims"]) - 1
    with tempfile.TemporaryDirectory(prefix="bench-layers-") as tmp:
        path = save_trace or os.path.join(tmp, "trace.json.gz")
        res = trace_program.trace_cell(cell, seed, rounds, devices,
                                       save_trace=path)
        tr = trace.read_saved(path)
    columns = parallel.probe_columns(num_layers) \
        if hasattr(parallel, "probe_columns") else []
    return {"workload": cell.name, "seed": seed, "rounds": rounds,
            **split(tr, rounds, num_layers, res["metrics"]),
            "searches": search_counts(res["counters"], columns),
            "program": res}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--save-trace", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".cache" / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import run
    from harness import setup, spec
    setup.use_program()
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.cell(args.workload)
    devices = run.devices_for(cell.chips)
    if devices is None:
        return 2
    enable_compile_cache()
    print(json.dumps(trace_layers(cell, args.seed, args.rounds, devices,
                                  save_trace=args.save_trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
