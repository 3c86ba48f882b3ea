"""Chip smoke run: the community-ADMM trainer and server on a TPU at the
paper's widths, checked against float32 references.

    python chip_smoke.py              # one chip: trainer + server phases
    python chip_smoke.py --chips 4    # the 4-shard fused path vs 1 device

One chip: the synthetic Amazon Photo graph at full size (7,650 nodes, 745
features, 8 classes), the paper's 745 → 1000 → 8 GCN with ν = ρ = 1e-4,
partitioned into the paper's M=3 communities, on the packed trainer with
the Pallas kernels.  It takes 5 ADMM steps, proves the compiled step runs
the Pallas kernel (``tpu_custom_call``), compares W, Z and the Lagrangian
with the same trainer on the einsum path and with the serial trainer, and
serves 256 Zipf requests through ``CommunityServer`` (the packed halo
kernel), compared with the dense forward pass.

``--chips 4`` runs only the multi-chip path: M=4 communities on a
4-device mesh with ``fused=True`` (ppermute exchange, packed and fused
kernels) against the same configuration on one device.

Every numeric phase runs under ``jax.default_matmul_precision("highest")``
so the kernel, the einsum path and the serial trainer all compute float32
products; the tolerances below are set from float32 rounding, not fitted
to a run.  Any failed phase exits non-zero; the last line of a passing run
is one JSON object naming the device.  There is no CPU fallback: without
a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

F32_EPS = float(np.finfo(np.float32).eps)
# Same blocked math, different summation order (kernel vs einsum; 4 shards
# vs 1 device): a few thousand f32 roundings per contraction, carried
# through 5 ADMM steps.
SAME_MATH_RTOL = 1e3 * F32_EPS           # ≈ 1.2e-4
# Blocked community aggregation vs the serial trainer's dense Ã products:
# every sum regroups, ten times the slack of the same-math comparison.
SERIAL_RTOL = 1e4 * F32_EPS              # ≈ 1.2e-3
# Served embeddings: one forward pass, halo + self split vs dense Ã.
SERVE_RTOL = 1e3 * F32_EPS
STEPS = 5
REQUESTS = 256
# what a Pallas kernel lowers to in compiled TPU HLO
KERNEL_MARK = "tpu_custom_call"


class PhaseError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(a, b) -> float:
    """‖a − b‖ / ‖b‖ over whole arrays (Frobenius)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# phases (each importable; tests drive them on a tiny CPU graph)
# ---------------------------------------------------------------------------

def paper_workload(num_parts: int):
    """Amazon Photo at full size with the paper's widths and partition."""
    from repro.configs import gcn_paper
    from repro.core import graph

    g = graph.synthetic_sbm("amazon_photo", seed=0)
    cfg, admm = gcn_paper.config("amazon_photo")
    part = graph.partition_graph(g.num_nodes, g.edges, num_parts, seed=0,
                                 method="multilevel")
    return g, part, cfg, admm


def build_trainer(g, part, cfg, admm, num_parts: int, *, use_kernel: bool,
                  fused: bool = False, mesh=None):
    from repro.core.parallel import ParallelADMMTrainer, TrainerConfig

    config = TrainerConfig.packed(partitioner="multilevel",
                                  use_kernel=use_kernel, fused=fused)
    return ParallelADMMTrainer(cfg, admm, g, num_parts=num_parts, seed=0,
                               part=part, mesh=mesh, config=config)


def compile_step(trainer) -> tuple[str, float]:
    """Compile the trainer's step once; returns (HLO text, seconds)."""
    t0 = time.perf_counter()
    text = trainer._step.lower(*trainer._analysis_args).compile().as_text()
    return text, time.perf_counter() - t0


def train(trainer, steps: int = STEPS) -> dict:
    """``trainer.train`` for ``steps`` ADMM steps; the Lagrangian must stay
    finite.  Step times exclude the first (it loads the program)."""
    out = trainer.train(steps)
    lag = np.asarray(out.lagrangian)
    if not np.all(np.isfinite(lag)):
        raise PhaseError(f"non-finite Lagrangian {lag.tolist()}")
    times = out.epoch_time_s[1:] or out.epoch_time_s
    return {"lagrangian": lag.tolist(), "step_s": times,
            "train_acc": out.train_acc[-1], "test_acc": out.test_acc[-1]}


def step_sizes(st) -> dict:
    """Per layer, the W line search's τ and the Z line search's θ (the
    largest over community lanes) that a state carries into its next
    step: where two trajectories part, these show whether a search
    accepted a different step."""
    return {"tau": [float(t) for t in st.taus],
            "theta": [float(np.max(np.asarray(t))) for t in st.thetas]}


def trainer_arrays(trainer, state=None) -> dict:
    """W, node-order Z and U of a parallel trainer's state, on the host,
    and its step sizes."""
    st = trainer.state if state is None else state
    layout, dl = trainer.layout, trainer.packed_layout

    def nodes(x):
        return layout.unpack(dl.unpack_state(np.asarray(x)))
    return {"w": [np.asarray(w) for w in st.weights],
            "z": [nodes(z) for z in st.zs], "u": [nodes(st.u)],
            **step_sizes(st)}


def serial_arrays(serial) -> dict:
    st = serial.state
    return {"w": [np.asarray(w) for w in st.weights],
            "z": [np.asarray(z) for z in st.zs], "u": [np.asarray(st.u)],
            **step_sizes(st)}


def compare(a: dict, b: dict, lag_a: float, lag_b: float) -> dict:
    errs = {k: max(rel_err(x, y) for x, y in zip(a[k], b[k]))
            for k in ("w", "z", "u")}
    errs["lagrangian"] = abs(lag_a - lag_b) / max(abs(lag_b), 1e-30)
    return errs


def transfer_state(src, dst, state):
    """``src``'s state re-laid out and placed for ``dst``: same graph and
    communities, possibly another shard count."""
    from repro.core.parallel import ParallelState, place_on_mesh

    def plane(x):
        return dst.packed_layout.pack_state(
            src.packed_layout.unpack_state(np.asarray(x)))
    host = ParallelState(tuple(np.asarray(w) for w in state.weights),
                         tuple(plane(z) for z in state.zs), plane(state.u),
                         tuple(np.asarray(t) for t in state.taus),
                         tuple(np.asarray(t) for t in state.thetas),
                         np.zeros(dst.state.probes.shape, np.int32))
    return place_on_mesh(dst.mesh, host, dst.state_spec)


def serial_state(trainer, state):
    """A parallel trainer's state as the serial trainer's: node-order Z/U,
    and per layer the one θ all community lanes carry (the serial Z
    update line-searches one global θ; the lanes start equal and, unless
    a lane's own search diverges, stay equal — the spread is reported)."""
    import jax.numpy as jnp

    from repro.core.subproblems import ADMMState

    arr = trainer_arrays(trainer, state)
    thetas = [np.asarray(t) for t in state.thetas]
    spread = max(float(np.ptp(t)) for t in thetas)
    return ADMMState(tuple(jnp.asarray(w) for w in arr["w"]),
                     tuple(jnp.asarray(z) for z in arr["z"]),
                     jnp.asarray(arr["u"][0]),
                     tuple(jnp.asarray(np.asarray(t)) for t in state.taus),
                     tuple(jnp.asarray(t.max()) for t in thetas)), spread


def parallel_reference(a, b):
    """``b``'s step program applied to ``a``'s state (re-laid out)."""
    def run(state):
        b.state = b._step(transfer_state(a, b, state))
        return trainer_arrays(b), float(b._lagrangian(b.state)), 0.0
    return run


def serial_reference(a, serial):
    """The serial trainer's step applied to ``a``'s state."""
    def run(state):
        serial.state, spread = serial_state(a, state)
        serial.step()
        lag = serial._lagr(serial.a_tilde, serial.z0, serial.labels,
                           serial.train_mask, serial.state)
        return serial_arrays(serial), float(lag), spread
    return run


def stepwise(a, references: dict, steps: int = STEPS) -> dict:
    """Advance trainer ``a`` ``steps`` times; before each step, run every
    reference step on the same state and compare its result (W, Z, U)
    with ``a``'s, and the Lagrangian each side evaluates on its own
    result.  Each step's line searches start from identical iterates, so
    one accept decision flipped by float noise cannot carry a trajectory
    away.  Returns, per reference, the worst errors over the steps (and
    the widest θ spread across community lanes it was handed)."""
    worst: dict = {name: {} for name in references}
    for _ in range(steps):
        outs = {name: ref(a.state) for name, ref in references.items()}
        a.step()
        mine, lag = trainer_arrays(a), float(a._lagrangian(a.state))
        for name, (arrays, ref_lag, spread) in outs.items():
            errs = compare(mine, arrays, lag, ref_lag)
            errs["theta_spread"] = spread
            w = worst[name]
            worst[name] = {k: max(v, w.get(k, 0.0)) for k, v in errs.items()}
    return worst


def aggregation_error(trainer, c: int, seed: int = 0) -> float:
    """The trainer's ELL aggregation (kernel or einsum, by its config) on
    its own adjacency and a random (M, n_pad, c) Z, against float64."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    d = trainer.data
    z = np.random.default_rng(seed).normal(
        size=(d.row_mask.shape[0], trainer.layout.n_pad, c))
    z = (z * np.asarray(d.row_mask)[..., None]).astype(np.float32)
    agg = kops.community_spmm_ell if trainer.config.use_kernel \
        else kref.community_spmm_ell_einsum
    out = jax.jit(agg)(d.ell_blocks, d.ell_indices, d.ell_mask,
                       jnp.asarray(z), d.row_counts, d.nbr_counts)
    idx, msk = np.asarray(d.ell_indices), np.asarray(d.ell_mask)
    # batched BLAS matmuls (a plain einsum here runs an unblocked loop)
    expect = np.matmul(np.asarray(d.ell_blocks, np.float64),
                       z.astype(np.float64)[idx] * msk[..., None, None])
    return rel_err(out, expect.sum(axis=1))


def check(name: str, errs: dict, tol: float) -> None:
    worst = max(v for k, v in errs.items() if k != "theta_spread")
    log(f"{name}: max rel err " + "  ".join(
        f"{k.upper() if len(k) == 1 else k} {v:.3e}" for k, v in errs.items())
        + f"  (tol {tol:.3e})")
    if not worst <= tol:
        raise PhaseError(f"{name}: relative error {worst:.3e} above {tol:.3e}")


def run_serial(g, cfg, admm, steps: int = STEPS):
    from repro.core.serial import SerialADMMTrainer

    serial = SerialADMMTrainer(cfg, admm, g, seed=0)
    out = serial.train(steps)
    return serial, out.lagrangian[-1]


def serve_phase(trainer, requests: int = REQUESTS, batch: int = 64,
                seed: int = 1) -> dict:
    """Answer a Zipf request stream through ``CommunityServer`` and compare
    with the dense forward pass over the same weights."""
    import jax.numpy as jnp

    from repro.core import gcn, graph
    from repro.serve import CommunityServer, ServeConfig, zipf_node_stream

    g = trainer.graph
    server = CommunityServer.from_trainer(trainer, ServeConfig())
    halo_hlo = server.halo_path_lowered(layer=1).compile().as_text()
    stream = zipf_node_stream(g.num_nodes, requests, seed=seed)
    t0 = time.perf_counter()
    served = np.concatenate([server.serve(stream[i:i + batch])
                             for i in range(0, requests, batch)])
    serve_s = time.perf_counter() - t0
    a = jnp.asarray(graph.normalized_adjacency(g.num_nodes, g.edges))
    dense = gcn.forward(trainer.cfg, a, jnp.asarray(g.features),
                        [jnp.asarray(w) for w in trainer.state.weights])[-1]
    expect = np.asarray(dense)[stream]
    if not np.all(np.isfinite(served)):
        raise PhaseError("non-finite served embeddings")
    return {"rel_err": rel_err(served, expect), "serve_s": serve_s,
            "halo_kernel": KERNEL_MARK in halo_hlo,
            "stats": server.stats()}


def device_report(devices) -> list[dict]:
    rows = []
    for d in devices:
        st = d.memory_stats() or {}
        rows.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                     "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return rows


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def parity(a, refs: dict, tols: dict) -> None:
    """The comparison phase: each reference's own 5-step trajectory
    against ``a``'s (reported — line-search decisions may differ), then
    the gated stepwise comparison over the same 5 steps from ``a``'s
    initial state."""
    worst = stepwise(a, {k: r for k, (r, _) in refs.items()})
    lag_a = float(a._lagrangian(a.state))
    mine = trainer_arrays(a)
    for name, (_, (arrays, lag)) in refs.items():
        errs = compare(mine, arrays, lag_a, lag)
        log(f"{name}, own trajectory after {STEPS} steps (not gated): "
            + "  ".join(f"{k} {v:.3e}" for k, v in errs.items())
            + "; step sizes " + "  ".join(
                f"{k} {mine[k]} vs {arrays[k]}" for k in ("tau", "theta")))
    for name, errs in worst.items():
        check(f"{name}, stepwise over {STEPS} steps", errs, tols[name])


def one_chip() -> None:
    import jax

    g, part, cfg, admm = paper_workload(3)
    log(f"graph N={g.num_nodes} E={g.num_edges} widths {cfg.layer_dims} "
        f"nu={admm.nu} rho={admm.rho}, M=3 communities sizes "
        f"{np.bincount(part).tolist()}")

    tr = build_trainer(g, part, cfg, admm, 3, use_kernel=True)
    log(f"layout n_pad={tr.layout.n_pad} max_deg="
        f"{tr.data.ell_mask.shape[1]} mesh={dict(tr.mesh.shape)}")
    hlo, compile_s = compile_step(tr)
    if KERNEL_MARK not in hlo:
        raise PhaseError(f"the compiled step has no {KERNEL_MARK}: the "
                         "Pallas kernel did not lower")
    log(f"step compile {compile_s:.2f} s; compiled step has "
        f"{hlo.count(KERNEL_MARK)} {KERNEL_MARK} site(s)")
    ein = build_trainer(g, part, cfg, admm, 3, use_kernel=False)
    for c in cfg.layer_dims:
        errs = {"kernel": aggregation_error(tr, c),
                "einsum": aggregation_error(ein, c)}
        log(f"ELL aggregation at C={c} vs float64: kernel "
            f"{errs['kernel']:.3e}  einsum {errs['einsum']:.3e}  "
            f"(tol {SAME_MATH_RTOL:.3e})")
        if not max(errs.values()) <= SAME_MATH_RTOL:
            raise PhaseError(f"aggregation at C={c} off by {errs}")

    res_e = train(ein)
    log(f"einsum trainer: step s {res_e['step_s']}")
    serial, lag_s = run_serial(g, cfg, admm)
    refs = {"kernel vs einsum": (parallel_reference(tr, ein),
                                 (trainer_arrays(ein),
                                  res_e["lagrangian"][-1])),
            "kernel vs serial": (serial_reference(tr, serial),
                                 (serial_arrays(serial), lag_s))}
    parity(tr, refs, {"kernel vs einsum": SAME_MATH_RTOL,
                      "kernel vs serial": SERIAL_RTOL})
    del ein, serial, refs

    res = train(tr)
    log(f"kernel trainer: {STEPS} more steps through train(), Lagrangian "
        f"{res['lagrangian']}")
    log(f"kernel trainer: step s {res['step_s']} (median "
        f"{float(np.median(res['step_s'])):.4f} s), train acc "
        f"{res['train_acc']:.4f} test acc {res['test_acc']:.4f}")

    sv = serve_phase(tr)
    log(f"served {REQUESTS} Zipf requests in {sv['serve_s']:.3f} s "
        f"(cold caches included), hit rate "
        f"{sv['stats']['requests']['hit_rate']}, halo kernel "
        f"{KERNEL_MARK}={sv['halo_kernel']}")
    log(f"served vs dense forward: rel err {sv['rel_err']:.3e} "
        f"(tol {SERVE_RTOL:.3e})")
    if not sv["halo_kernel"]:
        raise PhaseError(f"the serving halo program has no {KERNEL_MARK}")
    if not sv["rel_err"] <= SERVE_RTOL:
        raise PhaseError(f"served embeddings off by {sv['rel_err']:.3e}")
    dev = jax.devices()[0]
    log(f"device {dev.device_kind}: memory {device_report([dev])[0]}")


def four_chips() -> None:
    import jax
    from jax.sharding import AxisType

    from repro.core.parallel import AXIS

    devices = jax.devices()
    if len(devices) < 4:
        raise PhaseError(f"--chips 4 needs 4 devices, found {len(devices)}")
    g, part, cfg, admm = paper_workload(4)
    log(f"graph N={g.num_nodes} widths {cfg.layer_dims}, M=4 communities "
        f"sizes {np.bincount(part).tolist()}")
    mesh4 = jax.make_mesh((4,), (AXIS,), (AxisType.Auto,),
                          devices=devices[:4])
    tr4 = build_trainer(g, part, cfg, admm, 4, use_kernel=True, fused=True,
                        mesh=mesh4)
    log(f"4-shard trainer built: n_pad={tr4.layout.n_pad}; per-device "
        f"bytes {device_report(devices[:4])}")
    hlo, compile_s = compile_step(tr4)
    n_perm = hlo.count("collective-permute")
    log(f"4-shard step compile {compile_s:.2f} s: "
        f"{hlo.count(KERNEL_MARK)} {KERNEL_MARK}, {n_perm} "
        f"collective-permute site(s)")
    if KERNEL_MARK not in hlo or n_perm == 0:
        raise PhaseError("the 4-shard step lacks the kernel or the "
                         "ppermute exchange")

    mesh1 = jax.make_mesh((1,), (AXIS,), (AxisType.Auto,),
                          devices=devices[:1])
    tr1 = build_trainer(g, part, cfg, admm, 4, use_kernel=True, fused=True,
                        mesh=mesh1)
    res1 = train(tr1)
    log(f"1-device: Lagrangian {res1['lagrangian']}, step s {res1['step_s']}")
    parity(tr4, {"4 shards vs 1 device": (
        parallel_reference(tr4, tr1),
        (trainer_arrays(tr1), res1["lagrangian"][-1]))},
        {"4 shards vs 1 device": SAME_MATH_RTOL})
    del tr1

    res4 = train(tr4)
    log(f"4-shard: {STEPS} more steps through train(), Lagrangian "
        f"{res4['lagrangian']}, step s {res4['step_s']}")
    shard_rows = {str(s.device.id): s.data.shape[0]
                  for s in tr4.state.zs[0].addressable_shards}
    log(f"Z_1 plane rows per device {shard_rows}")
    log(f"4-shard per-device bytes after training "
        f"{device_report(devices[:4])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard path and its 1-device "
                         "comparison")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform} devices; nothing was run")
        return 2
    log(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    try:
        with jax.default_matmul_precision("highest"):
            four_chips() if args.chips == 4 else one_chip()
    except PhaseError as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
