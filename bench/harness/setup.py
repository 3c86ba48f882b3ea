"""Set-up of one cell: the graph, its cached partition, the trainer.

The graph and its partition are the deployment's dataset: fixed per
configuration, the same in every run.  ``--seed`` reaches only the
trainer's initial weights.  The partition is computed by the program's
own partitioner and cached under ``bench/.cache/partition``, keyed by the
configuration, the community count, the graph's edges and the source of
the partitioner, so a change to the partitioner is measured again.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pathlib
import sys
import time

import numpy as np

from harness import sbm

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CACHE = BENCH / ".cache"


def use_program() -> None:
    """Put the program under test (``<checkout>/src``) on the path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def read_json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Timer:
    """Seconds spent per named phase, in the order they ran."""

    def __init__(self):
        self.phases: dict[str, float] = {}

    def add(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + seconds

    @contextlib.contextmanager
    def time(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)


def _partitioner_sources() -> bytes:
    use_program()
    import repro.core.graph as graph_mod
    import repro.sharding.multilevel as ml_mod
    return b"".join(pathlib.Path(m.__file__).read_bytes()
                    for m in (graph_mod, ml_mod))


def partition_key(config_name: str, g: sbm.SBMGraph, num_parts: int,
                  method: str) -> str:
    h = hashlib.sha256()
    h.update(f"{config_name}|{g.num_nodes}|{num_parts}|{method}".encode())
    h.update(np.ascontiguousarray(g.edges).tobytes())
    h.update(_partitioner_sources())
    return h.hexdigest()[:20]


def partition(config_name: str, g: sbm.SBMGraph, num_parts: int,
              method: str) -> tuple[np.ndarray, bool]:
    """The program's partition of ``g``; returns (part, cache hit)."""
    key = partition_key(config_name, g, num_parts, method)
    path = CACHE / "partition" / f"{config_name}-m{num_parts}-{key}.npy"
    if path.exists():
        return np.load(path), True
    use_program()
    from repro.core import graph
    part = graph.partition_graph(g.num_nodes, g.edges, num_parts, seed=0,
                                 method=method)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npy")
    np.save(tmp, np.asarray(part, dtype=np.int32))
    os.replace(tmp, path)
    return np.asarray(part, dtype=np.int32), False


def program_graph(g: sbm.SBMGraph):
    """The generated graph as the program's ``Graph``."""
    use_program()
    from repro.core import graph
    return graph.Graph(edges=g.edges, features=g.features, labels=g.labels,
                       train_mask=g.train_mask, test_mask=g.test_mask,
                       num_classes=g.num_classes)


def program_configs(config: dict):
    use_program()
    from repro.core.gcn import GCNConfig
    from repro.core.subproblems import ADMMConfig
    model, admm = config["model"], config["admm"]
    return (GCNConfig(layer_dims=tuple(model["layer_dims"]),
                      activation=model["activation"]),
            ADMMConfig(**admm))


def make_mesh(chips: int):
    use_program()
    import jax
    from jax.sharding import AxisType

    from repro.core.parallel import AXIS
    return jax.make_mesh((chips,), (AXIS,), (AxisType.Auto,),
                         devices=jax.devices()[:chips])


def build_trainer(config: dict, traffic: dict, g: sbm.SBMGraph,
                  part: np.ndarray, chips: int, seed: int):
    """The timed trainer, on a mesh of the cell's chips, with its initial
    state drawn from ``seed`` by its own constructor."""
    use_program()
    from repro.core import parallel

    tconf = parallel.TrainerConfig.packed(
        partitioner=traffic["partitioner"], use_kernel=traffic["use_kernel"],
        fused=traffic["fused"], batch_fraction=traffic["batch_fraction"])
    cfg, admm = program_configs(config)
    return parallel.ParallelADMMTrainer(
        cfg, admm, program_graph(g), num_parts=traffic["num_parts"],
        mesh=make_mesh(chips), seed=seed, part=part, config=tconf)


def host_state(trainer, state=None) -> dict:
    """W, node-order Z and U of a trainer state, copied to the host."""
    st = trainer.state if state is None else state
    layout, dl = trainer.layout, trainer.packed_layout

    def nodes(x):
        return layout.unpack(dl.unpack_state(np.asarray(x)))
    return {"w": [np.asarray(w) for w in st.weights],
            "z": [nodes(z) for z in st.zs], "u": nodes(st.u),
            "tau": [float(t) for t in st.taus]}
