"""Serial ADMM trainer (paper §4.1: one community, single agent).

The math is the global form of Algorithm 1; `parallel.py` implements the
community-distributed form and a test asserts both produce identical updates
(the paper's 'no performance loss' claim for community splitting).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import gcn, graph, subproblems

Array = jax.Array


@dataclasses.dataclass
class TrainLog:
    epoch: list = dataclasses.field(default_factory=list)
    train_acc: list = dataclasses.field(default_factory=list)
    test_acc: list = dataclasses.field(default_factory=list)
    lagrangian: list = dataclasses.field(default_factory=list)
    residual: list = dataclasses.field(default_factory=list)
    epoch_time_s: list = dataclasses.field(default_factory=list)

    def as_dict(self):
        return dataclasses.asdict(self)


class SerialADMMTrainer:
    """Single-agent ADMM GCN trainer (the paper's 'Serial ADMM')."""

    def __init__(self, cfg: gcn.GCNConfig, admm: subproblems.ADMMConfig,
                 g: graph.Graph, seed: int = 0):
        self.cfg, self.admm, self.graph = cfg, admm, g
        self.a_tilde = jnp.asarray(
            graph.normalized_adjacency(g.num_nodes, g.edges))
        self.z0 = jnp.asarray(g.features)
        self.labels = jnp.asarray(g.labels)
        self.train_mask = jnp.asarray(g.train_mask, dtype=jnp.float32)
        self.test_mask = jnp.asarray(g.test_mask, dtype=jnp.float32)
        self.state = subproblems.init_state(
            cfg, admm, self.a_tilde, self.z0, jax.random.key(seed))

        self._step = jax.jit(partial(
            subproblems.admm_iteration, cfg, admm))
        self._lagr = jax.jit(partial(
            subproblems.lagrangian_value, cfg, admm))

        # graph arrays are arguments, not closed-over constants (a dense
        # Ã baked into the program is N² floats of literal)
        @jax.jit
        def _metrics(a_tilde, z0, labels, train_mask, test_mask,
                     state: subproblems.ADMMState):
            logits = gcn.forward(cfg, a_tilde, z0, state.weights)[-1]
            z_pen = state.zs[-2] if cfg.num_layers >= 2 else z0
            res = state.zs[-1] - a_tilde @ z_pen @ state.weights[-1]
            return (gcn.accuracy(logits, labels, train_mask),
                    gcn.accuracy(logits, labels, test_mask),
                    jnp.linalg.norm(res))

        self._metrics = partial(_metrics, self.a_tilde, self.z0,
                                self.labels, self.train_mask, self.test_mask)

    def step(self) -> None:
        self.state = self._step(self.a_tilde, self.z0, self.labels,
                                self.train_mask, self.state)

    def train(self, epochs: int, log_every: int = 1,
              verbose: bool = False) -> TrainLog:
        log = TrainLog()
        for epoch in range(epochs):
            t0 = time.perf_counter()
            self.step()
            jax.block_until_ready(self.state.zs[-1])
            dt = time.perf_counter() - t0
            if epoch % log_every == 0 or epoch == epochs - 1:
                tr, te, res = self._metrics(self.state)
                lag = self._lagr(self.a_tilde, self.z0, self.labels,
                                 self.train_mask, self.state)
                log.epoch.append(epoch)
                log.train_acc.append(float(tr))
                log.test_acc.append(float(te))
                log.lagrangian.append(float(lag))
                log.residual.append(float(res))
                log.epoch_time_s.append(dt)
                if verbose:
                    print(f"[serial-admm] epoch {epoch:3d} train {tr:.3f} "
                          f"test {te:.3f} lagr {lag:.4f} res {res:.3e} "
                          f"({dt*1e3:.1f} ms)")
        return log


# ---------------------------------------------------------------------------
# SGD-family baselines (paper §4.2 comparison methods)
# ---------------------------------------------------------------------------

class BaselineTrainer:
    """Backprop GCN training with the paper's comparison optimizers."""

    def __init__(self, cfg: gcn.GCNConfig, g: graph.Graph, optimizer: str,
                 lr: float, seed: int = 0):
        from repro.optim import optimizers
        self.cfg, self.graph = cfg, g
        self.a_tilde = jnp.asarray(
            graph.normalized_adjacency(g.num_nodes, g.edges))
        self.z0 = jnp.asarray(g.features)
        self.labels = jnp.asarray(g.labels)
        self.train_mask = jnp.asarray(g.train_mask, dtype=jnp.float32)
        self.test_mask = jnp.asarray(g.test_mask, dtype=jnp.float32)
        self.weights = gcn.init_weights(cfg, jax.random.key(seed))
        self.opt = optimizers.make(optimizer, lr)
        self.opt_state = self.opt.init(self.weights)

        @jax.jit
        def _step(weights, opt_state):
            loss, grads = jax.value_and_grad(
                lambda ws: gcn.loss_fn(cfg, self.a_tilde, self.z0, ws,
                                       self.labels, self.train_mask))(weights)
            updates, opt_state = self.opt.update(grads, opt_state, weights)
            weights = jax.tree.map(lambda w, u: w + u, weights, updates)
            return weights, opt_state, loss

        @jax.jit
        def _metrics(weights):
            logits = gcn.forward(cfg, self.a_tilde, self.z0, weights)[-1]
            return (gcn.accuracy(logits, self.labels, self.train_mask),
                    gcn.accuracy(logits, self.labels, self.test_mask))

        self._step, self._metrics = _step, _metrics

    def train(self, epochs: int, verbose: bool = False) -> TrainLog:
        log = TrainLog()
        for epoch in range(epochs):
            t0 = time.perf_counter()
            self.weights, self.opt_state, loss = self._step(
                self.weights, self.opt_state)
            jax.block_until_ready(self.weights[-1])
            dt = time.perf_counter() - t0
            tr, te = self._metrics(self.weights)
            log.epoch.append(epoch)
            log.train_acc.append(float(tr))
            log.test_acc.append(float(te))
            log.lagrangian.append(float(loss))
            log.residual.append(0.0)
            log.epoch_time_s.append(dt)
            if verbose:
                print(f"[{'baseline'}] epoch {epoch:3d} loss {loss:.4f} "
                      f"train {tr:.3f} test {te:.3f}")
        return log
