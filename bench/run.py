"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs``), a traffic mix
(``bench/traffic``) and its chips (``BENCHMARK.json``).  Set-up generates
the configuration's graph, partitions it with the program's partitioner
(cached under ``bench/.cache/partition``), builds the program's
``ParallelADMMTrainer`` on a mesh of the cell's chips with its initial
weights drawn from ``--seed``, and drives it through its first rounds and a
fixed number of warm rounds by ``train()``, the call the window makes.
The window then calls ``train()`` in chunks until ``--seconds`` have
passed.  After the window the run compares the trainer's first rounds with
the plain reference (``harness/reference.py``) from the same seed.

``--trace 0`` reports the end-to-end metrics; ``--trace 1`` traces a slice
of the window with the profiler and reports the per-layer metrics, each
read by ``bench/metrics/<name>.py``.  The last line of standard output is
one JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of that object.

There is no fallback: without the accelerator the cell asks for, or on a
device kind with no known peaks, the run exits non-zero and prints no
result.  JAX's compile cache lives in ``bench/.cache/jax``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


# The harness's procedure, the same in every cell.  The limits in
# ``bench/limits`` were read over CHECK_STEPS rounds.
CHECK_STEPS = 3       # rounds compared with the reference
WARM_ROUNDS = 6       # rounds after those, before the window
CHUNK_S = 0.5         # the window calls train() in chunks of about this
TRACE_SLICE_S = 2.5   # seconds of the window a --trace 1 run profiles


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs JAX lowers, and the persistent cache's hits
    and misses, through ``jax.monitoring``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.counts = {self.LOWER: 0, self.HIT: 0, self.MISS: 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, key, **_):
        if key in self.counts:
            self.counts[key] += 1

    def _duration(self, key, _secs, **_):
        self._event(key)

    @property
    def lowered(self) -> int:
        return self.counts[self.LOWER]


# jax.monitoring listeners live as long as the process and cannot be taken
# back, so one counter serves every run a process makes
_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def devices_for(chips: int):
    """The cell's devices, or None when JAX finds no TPU, too few chips or
    a device kind with no known peaks."""
    import jax

    from harness import work
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {devs[0].platform} devices; nothing was run")
        return None
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devs)}")
        return None
    if devs[0].device_kind not in work.PEAKS:
        log(f"no peaks for device kind {devs[0].device_kind!r}")
        return None
    return devs


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def check_rounds(trainer, steps: int) -> tuple[list, list]:
    """The trainer's first ``steps`` rounds through ``train()``: the host
    copies of S₀ … S_k and the Lagrangian ``train()`` reported for each."""
    from harness import setup
    states, losses = [setup.host_state(trainer)], []
    for _ in range(steps):
        out = trainer.train(1)
        losses.append(float(out.lagrangian[-1]))
        states.append(setup.host_state(trainer))
    return states, losses


def reference_rounds(ref, seed: int, steps: int) -> tuple:
    """The reference's S₀ … S_k and Lagrangians from ``seed``."""
    from harness import reference
    st = ref.initial(seed)
    states, losses = [reference.host(st)], []
    for _ in range(steps):
        st = ref.step(st)
        states.append(reference.host(st))
        losses.append(ref.lagrangian(st))
    return states, losses


def run(cell, seed: int, seconds: float, traced: bool, *,
        t_start: float | None = None, devices=None,
        save_trace: str | None = None) -> dict:
    """One run of ``cell``; returns the result object.  ``devices`` are the
    devices JAX reports (``jax.devices()`` by default); the trainer's mesh
    takes the first ``cell.chips`` of them."""
    import jax

    from harness import compare, reference, sbm, setup, trace, work

    t_start = T_START if t_start is None else t_start
    devices = jax.devices() if devices is None else devices
    counter = compile_counter()
    tr, cfg = cell.traffic, cell.config
    timer = setup.Timer()
    precision = cfg["precision"]["matmul"]
    hits0, miss0 = counter.counts[counter.HIT], counter.counts[counter.MISS]

    with jax.default_matmul_precision(precision):
        with timer.time("generate"):
            g = sbm.generate(cfg["data"], seed=cfg["data"]["generator_seed"])
        with timer.time("partition"):
            part, part_hit = setup.partition(cfg["name"], g, tr["num_parts"],
                                             tr["partitioner"])
        # the trainer's constructor: the community layout, device data,
        # placement, initial state and program set-up
        with timer.time("construct"):
            trainer = setup.build_trainer(cfg, tr, g, part, cell.chips, seed)
        with timer.time("compile"):
            states, losses = check_rounds(trainer, 1)
        with timer.time("warm"):
            more_states, more_losses = check_rounds(trainer, CHECK_STEPS - 1)
            states += more_states[1:]
            losses += more_losses
            t0 = time.perf_counter()
            trainer.train(WARM_ROUNDS)
            jax.block_until_ready(trainer.state)
            warm_round_s = (time.perf_counter() - t0) / WARM_ROUNDS
        chunk = max(1, round(CHUNK_S / warm_round_s))
        setup_s = time.perf_counter() - t_start
        setup_line = {
            "setup_s": setup_s, "phases": timer.phases,
            "partition_cache": "hit" if part_hit else "miss",
            "compile_cache": {"hits": counter.counts[counter.HIT] - hits0,
                              "misses": counter.counts[counter.MISS] - miss0},
            "warm_round_ms": 1e3 * warm_round_s, "chunk_rounds": chunk}
        print(json.dumps({"setup": setup_line}), flush=True)
        log(f"setup {json.dumps(setup_line)}")

        # ---- the measured window --------------------------------------
        lowered0 = counter.lowered
        rounds = failed = slice_rounds = 0
        slice_s = min(TRACE_SLICE_S, seconds)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced \
            else None
        tracing = False
        if traced:
            trace.start(trace_dir)
            tracing = True
        t_w0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t_w0
            if tracing and now >= slice_s:
                trace.stop()
                tracing = False
                slice_rounds = rounds
            if now >= seconds and not tracing:
                break
            with trace.span("train_chunk"):
                out = trainer.train(chunk)
                jax.block_until_ready(trainer.state)
            rounds += chunk
            failed += sum(not math.isfinite(v) for v in out.lagrangian)
        window_s = time.perf_counter() - t_w0
        compiles = counter.lowered - lowered0
        taus = [float(t) for t in trainer.state.taus]
        mem = peak_bytes(trainer.mesh.devices.flat)
        log(f"window {window_s:.3f} s, {rounds} rounds in chunks of {chunk}, "
            f"{compiles} compilations inside, tau at the end {taus}, "
            f"memory peak {mem}")
        del trainer, out
        gc.collect()

        # ---- the comparison, once the program is freed ------------------
        t_c0 = time.perf_counter()
        ref = reference.Reference(cfg, g, part)
        ref_states, ref_losses = reference_rounds(ref, seed, CHECK_STEPS)
        values, detail = compare.numbers(states, losses, ref_states,
                                         ref_losses)
        correct, compared = compare.judge(values, cell.limits)
        log(f"compare {time.perf_counter() - t_c0:.3f} s; per leaf "
            f"{json.dumps(detail)}; program tau {states[-1]['tau']} "
            f"reference tau {ref_states[-1]['tau']}")

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct and failed == 0), "attempted": rounds,
              "failed": failed}
    if traced:
        tr_data = trace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if save_trace:
            trace.save(tr_data, save_trace)
        ctx = {"trace": tr_data, "rounds": slice_rounds, "chips": cell.chips,
               "n": g.num_nodes, "nnz": g.nnz,
               "dims": cfg["model"]["layer_dims"],
               "peak": work.peaks(dev0.device_kind)}
        metrics = {}
        for name, mod in cell.per_layer.items():
            v = mod.read(ctx)
            if v is not None:
                metrics[name] = {"value": float(v),
                                 "unit": cell.units[name]}
        device.update(busy_s=trace.busy_s(tr_data),
                      window_s=trace.window_s(tr_data))
        result.update(metrics=metrics, device=device,
                      breakdown=trace.breakdown(tr_data))
    else:
        metrics = {"round_ms": {"value": 1e3 * window_s / rounds,
                                "unit": "ms"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        result.update(metrics={k: v for k, v in metrics.items()
                               if k in cell.end_to_end}, device=device)
    result["window"] = {"seconds": window_s, "rounds": rounds,
                        "chunk_rounds": chunk, "compiles": compiles,
                        "traced_rounds": slice_rounds}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="with --trace 1, also write the reduced trace here "
                         "(gzipped JSON)")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".cache" / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from harness import setup, spec
    setup.use_program()
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.cell(args.workload)
    devices = devices_for(cell.chips)
    if devices is None:
        return 2
    log(f"device {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 devices=devices, save_trace=args.save_trace)
    for name, c in result["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
