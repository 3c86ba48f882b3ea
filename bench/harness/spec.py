"""Finding a cell's parts by name.

``BENCHMARK.json`` names the cells, configurations, traffic mixes and
metrics; everything that belongs to one of them sits in a file of its
own, found by that name:

* ``bench/configs/<config>.json``   the configuration, as it is run
* ``bench/traffic/<traffic>.json``  the traffic mix, read by ``run.py``
* ``bench/limits/<cell>.json``      the limits of the comparison
* ``bench/metrics/<metric>.py``     a per-layer metric's reader

A later cell, mix or metric is added by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib

from harness.setup import BENCH, ROOT, read_json


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # names of the end-to-end metrics it reports
    per_layer: dict        # name -> reader module
    units: dict            # metric name -> unit


def benchmark() -> dict:
    return read_json(ROOT / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reader(metric: str):
    return importlib.import_module(f"metrics.{metric}")


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = benchmark() if bench is None else bench
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    w = entries[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(BENCH / "limits" / f"{name}.json")["limits"],
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _applies(m, name)],
        per_layer={m["name"]: reader(m["name"]) for m in bench["per_layer"]
                   if _applies(m, name)},
        units={m["name"]: m["unit"]
               for m in bench["end_to_end"] + bench["per_layer"]})
