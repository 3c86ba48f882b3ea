"""The readings the comparison's limits are set from.  Not run by the
benchmark's own runs.

    python3 bench/readings.py --workload <cell> [--seeds 12] [--control 3] \
        [--faults 3] [--reassociated 12] [--out readings-<cell>.json]

For each seed the tool builds the cell's trainer, as a run does, drives it
through the check rounds by ``train()`` and compares them with the
reference's rounds from the same seed: the lower readings.  For the first
``--control`` seeds it also compares the control, the reference computed
one precision below the configuration's, in the program's place.  For the
first ``--reassociated`` seeds it compares the reference started from
another summation order (``Reference(reassociate_init=True)``): what a
sound rewrite that only reorders sums reads, which has to pass the limits.
For the first ``--faults`` seeds it compares the program with faults
planted in it: half of the training nodes left out, so the loss is the
mean over the rest; and, on more than one chip, the exchange between
chips left out.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402

# the next precision below the one the configuration states
LOWER = {"highest": "high"}


def program_readings(cell, g, part, seed: int):
    """The check rounds of a trainer built for ``seed``."""
    import gc

    from harness import setup
    trainer = setup.build_trainer(cell.config, cell.traffic, g, part,
                                  cell.chips, seed)
    out = run.check_rounds(trainer, run.CHECK_STEPS)
    del trainer
    gc.collect()
    return out


def half_batch(g):
    """``g`` with every other training node left out of the training set:
    the program then takes the mean of the loss over the rest."""
    mask = g.train_mask.copy()
    mask[mask.nonzero()[0][1::2]] = False
    return dataclasses.replace(g, train_mask=mask)


@contextlib.contextmanager
def no_exchange():
    """The program's packed exchange with its ppermute rounds left out:
    each shard keeps only its own communities' rows."""
    from harness import setup
    setup.use_program()
    from repro.core import messages
    original = messages.exchange_neighbors_packed

    def own_rows_only(plan, *args, **kw):
        return original(dataclasses.replace(plan, rounds=()), *args, **kw)

    messages.exchange_neighbors_packed = own_rows_only
    try:
        yield
    finally:
        messages.exchange_neighbors_packed = original


def readings(cell, seeds: list[int], n_control: int, n_faults: int,
             n_reassociated: int = 0, say=print) -> dict:
    import jax

    from harness import compare, reference, sbm, setup

    cfg, tr = cell.config, cell.traffic
    steps = run.CHECK_STEPS
    precision = cfg["precision"]["matmul"]
    out = {"cell": cell.name, "seeds": seeds, "check_steps": steps,
           "sound": [], "control": [], "reassociated": [], "faults": {}}
    with jax.default_matmul_precision(precision):
        g = sbm.generate(cfg["data"], seed=cfg["data"]["generator_seed"])
        part, _ = setup.partition(cfg["name"], g, tr["num_parts"],
                                  tr["partitioner"])
        ref = reference.Reference(cfg, g, part)
        others = {
            "control": (reference.Reference(cfg, g, part,
                                            precision=LOWER[precision]),
                        n_control),
            "reassociated": (reference.Reference(cfg, g, part,
                                                 reassociate_init=True),
                             n_reassociated)}
        refs = {}
        for i, seed in enumerate(seeds):
            refs[seed] = run.reference_rounds(ref, seed, steps)
            p = program_readings(cell, g, part, seed)
            vals, detail = compare.numbers(*p, *refs[seed])
            out["sound"].append({"seed": seed, **vals, "leaves": detail,
                                 "tau": p[0][-1]["tau"],
                                 "ref_tau": refs[seed][0][-1]["tau"]})
            say(f"sound seed {seed}: {vals}")
            for name, (other, n) in others.items():
                if i < n:
                    c = run.reference_rounds(other, seed, steps)
                    vals, detail = compare.numbers(*c, *refs[seed])
                    out[name].append({"seed": seed, **vals, "leaves": detail,
                                      "tau": c[0][-1]["tau"]})
                    say(f"{name} seed {seed}: {vals}")

        faults = {"half_batch": (half_batch(g), contextlib.nullcontext)}
        if cell.chips > 1:
            faults["no_exchange"] = (g, no_exchange)
        if not n_faults:
            faults = {}
        for name, (fg, planted) in faults.items():
            rows = []
            with planted():
                for seed in seeds[:n_faults]:
                    p = program_readings(cell, fg, part, seed)
                    vals, detail = compare.numbers(*p, *refs[seed])
                    rows.append({"seed": seed, **vals, "leaves": detail})
                    say(f"fault {name} seed {seed}: {vals}")
            out["faults"][name] = rows

    keys = ("loss", "first_update", "change")
    out["summary"] = {
        "lower": {k: max(r[k] for r in out["sound"]) for k in keys},
        "control": {k: min(r[k] for r in out["control"]) for k in keys}
        if out["control"] else {},
        "reassociated": {k: max(r[k] for r in out["reassociated"])
                         for k in keys} if out["reassociated"] else {},
        "faults": {name: {k: min(r[k] for r in rows) for k in keys}
                   for name, rows in out["faults"].items() if rows}}
    return out


def main(argv=None) -> int:
    import os
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--reassociated", type=int, default=12)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".cache" / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from harness import setup, spec
    setup.use_program()
    cell = spec.cell(args.workload)
    if run.devices_for(cell.chips) is None:
        return 2
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    res = readings(cell, seeds, args.control, args.faults, args.reassociated,
                   say=lambda m: print(f"[readings] {m}", flush=True))
    print(json.dumps(res["summary"]), flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(res, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
