"""A few ADMM rounds of one cell, traced with what the program records
about itself: its device scopes, host spans and line-search counters.

    python3 bench/trace_program.py --workload <cell> --seed <n> \
        [--rounds 3] [--save-trace out.json.gz] [--keep-xplane dir]

Set-up is ``run.py``'s (graph, cached partition, the trainer on the
cell's chips from ``--seed``), with the trainer's constructor inside
``repro.util.spans.recording()``.  After a compile round and
``run.WARM_ROUNDS`` warm rounds it reads ``state.probes``, profiles
``--rounds`` rounds of ``train()`` in one harness span, recording the
program's spans in memory too, and reads ``state.probes`` again.  The
trace is reduced by ``harness.xspace.load``, which adds each op's scope
path to ``trace.load``'s structure.

The last line of standard output is one JSON object: every reader in
``bench/metrics`` that finds something to read (the counters, set-up
spans and scopes as ``run.py`` does not pass them), the step program's
device time and the idle time per round, the device time of step ops
under none of the four ADMM scopes, the idle time per round under each of
``train()``'s host spans, the constructor on the host clock, and how far
each recorded span lies from its profiler event.  There is
no comparison with the reference and no fallback: without the cell's
chips it exits 2.  ``--save-trace`` writes the reduced trace with the
spans and counters (the recorded test trace); ``--keep-xplane`` copies
the profiler's file.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

SCOPES = ("admm_w", "admm_z", "admm_fista", "admm_dual")
TRAIN_SPANS = ("train.step", "train.wait", "train.eval", "train.sync")


def readers() -> dict:
    return {p.stem: importlib.import_module(f"metrics.{p.stem}")
            for p in sorted((BENCH / "metrics").glob("*.py"))
            if p.stem != "__init__"}


def span_rows(recorded) -> list:
    return [[s.name, s.parent, s.start_ns, s.end_ns] for s in recorded]


def span_offsets_ms(tr: dict, recorded: list) -> dict:
    """Largest |start| and |end| difference between each recorded span and
    the profiler's host event of the same name, matched in order."""
    base = tr.get("profile_start_ns")
    if base is None:
        return {}
    out = {}
    for name in sorted({s[0] for s in recorded}):
        mine = [s for s in recorded if s[0] == name]
        evs = sorted((h for h in tr["host"] if h[0] == name),
                     key=lambda h: h[1])
        if len(evs) != len(mine):
            out[name] = {"recorded": len(mine), "events": len(evs)}
            continue
        out[name] = {
            "start": max(abs(base + e[1] - s[2]) for e, s in zip(evs, mine))
            / 1e6,
            "end": max(abs(base + e[1] + e[2] - s[3])
                       for e, s in zip(evs, mine)) / 1e6}
    return out


def step_breakdown_ms(tr: dict, rounds: int) -> dict:
    """Device time per round of the step program, of its busy time under
    none of the four scopes (ops without a scope path count there too),
    and device idle time per round; means over devices."""
    from harness import program, trace
    if not tr["devices"] or rounds <= 0:
        return {}
    t0, t1 = trace.window_ns(tr)

    def step_s(dev):
        return trace.time_in(tr, dev, "modules",
                             lambda n: n.startswith("jit_step"))

    def other_s(dev):
        rec = tr["devices"][dev]
        step = trace.merge(trace.clip(
            [m for m in rec["modules"] if m[0].startswith("jit_step")],
            t0, t1))
        busy = trace.busy(tr, dev)
        step_busy = trace.subtract(step, trace.subtract(step, busy))
        scoped = trace.merge(trace.clip(
            [e for e in rec.get("scoped", [])
             if any(program.in_scope(e[0], s) for s in SCOPES)], t0, t1))
        return trace.total(trace.subtract(step_busy, scoped)) / 1e9

    def idle_s(dev):
        return trace.total(trace.subtract([(t0, t1)],
                                          trace.busy(tr, dev))) / 1e9
    per = {"jit_step_ms": step_s, "step_unscoped_ms": other_s,
           "idle_ms": idle_s}
    return {k: 1e3 * trace.mean_over_devices(tr, f) / rounds
            for k, f in per.items()}


def idle_by_span_ms(ctx) -> dict:
    """Device idle time per round under each of ``train()``'s host spans,
    and under none of them."""
    from harness import program
    out = {name: program.idle_under_spans_ms_per_round(ctx, (name,))
           for name in TRAIN_SPANS}
    under = program.idle_under_spans_ms_per_round(ctx, TRAIN_SPANS)
    if under is None or not ctx["trace"]["devices"]:
        return {}
    total = step_breakdown_ms(ctx["trace"], ctx["rounds"])["idle_ms"]
    return {**{k: v or 0.0 for k, v in out.items()}, "none": total - under}


def trace_cell(cell, seed: int, rounds: int, devices, *,
               save_trace: str | None = None,
               keep_xplane: str | None = None) -> dict:
    """The traced rounds of ``cell``; returns the result object."""
    import run
    from harness import sbm, setup, trace, work, xspace
    setup.use_program()
    import jax
    import numpy as np

    from repro.util import spans

    cfg, tr_cfg = cell.config, cell.traffic
    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        g = sbm.generate(cfg["data"], seed=cfg["data"]["generator_seed"])
        part, _ = setup.partition(cfg["name"], g, tr_cfg["num_parts"],
                                  tr_cfg["partitioner"])
        t0 = time.perf_counter()
        with spans.recording() as setup_spans:
            trainer = setup.build_trainer(cfg, tr_cfg, g, part, cell.chips,
                                          seed)
        construct_s = time.perf_counter() - t0
        trainer.train(1 + run.WARM_ROUNDS)
        jax.block_until_ready(trainer.state)

        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        before = np.asarray(trainer.state.probes).tolist()
        trace.start(log_dir)
        with spans.recording() as train_spans:
            with trace.span("train_chunk"):
                trainer.train(rounds)
                jax.block_until_ready(trainer.state)
        trace.stop()
        after = np.asarray(trainer.state.probes).tolist()

    tr = xspace.load(log_dir)
    if keep_xplane:
        os.makedirs(keep_xplane, exist_ok=True)
        shutil.copy(trace._xplane(log_dir),
                    os.path.join(keep_xplane, "trace.xplane.pb"))
    shutil.rmtree(log_dir, ignore_errors=True)
    counters = {"before": before, "after": after, "rounds": rounds}
    setup_rows, train_rows = span_rows(setup_spans), span_rows(train_spans)
    ctx = {"trace": tr, "rounds": rounds, "chips": cell.chips,
           "n": g.num_nodes, "nnz": g.nnz,
           "dims": cfg["model"]["layer_dims"],
           "peak": work.peaks(devices[0].device_kind),
           "counters": counters, "spans": setup_rows}
    metrics = {}
    for name, mod in readers().items():
        v = mod.read(ctx)
        if v is not None:
            metrics[name] = float(v)
    result = {"workload": cell.name, "seed": seed,
              "device": {"kind": devices[0].device_kind,
                         "count": len(devices)},
              "metrics": metrics,
              **step_breakdown_ms(tr, rounds),
              "idle_ms_by_span": idle_by_span_ms(ctx),
              "construct_s": construct_s, "counters": counters,
              "span_offset_ms": span_offsets_ms(tr, train_rows),
              "scoped_ops": {d: len(r.get("scoped", []))
                             for d, r in tr["devices"].items()}}
    if save_trace:
        trace.save(dict(tr, setup_spans=setup_rows, train_spans=train_rows,
                        counters=counters, rounds=rounds), save_trace)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--save-trace", default=None)
    ap.add_argument("--keep-xplane", default=None)
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
    result = trace_cell(cell, args.seed, args.rounds, devices,
                        save_trace=args.save_trace,
                        keep_xplane=args.keep_xplane)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
