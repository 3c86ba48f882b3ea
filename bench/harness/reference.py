"""Plain reference of the configuration: the paper's community ADMM
(arXiv:2112.09335, Algorithm 1) for a GCN, in straightforward float32
``jax.numpy`` with a dense normalized adjacency.

It imports nothing of the program.  Its inputs are the generated graph,
the configuration and the node → community assignment (the deployment's
communities: the paper's agents each own one).  Initial weights come from
the seed, by the same Glorot draw the configuration's model uses.

One round, as in Algorithm 1 with per-community agents:

* W_l (eq. 2): one majorize-minimize step on the global φ_l, τ_l doubled
  from τ_l/2 until the descent-lemma test holds (every agent evaluates the
  same global test, so there is one τ_l).
* Z_l, l < L (eq. 5/6): each community m takes its own step on ψ_{l,m},
  the objective with every other community held at Z^k (Jacobi), with its
  own θ_{l,m}.
* Z_L (eq. 7): per community, ``fista_iters`` FISTA steps on
  R(Z,Y) + ⟨U, Z−B⟩ + ρ/2‖Z−B‖², B = Ã Z_{L-1}^k W_L^{k+1}, each with its
  own Lipschitz backtracking.
* U (eq. 3): U + ρ (Z_L − Ã Z_{L-1} W_L) on the new iterates.

Every matrix product goes through one function: float32 at ``highest``
precision for the reference, and for the control the next precision
below, ``high``: three bfloat16 passes (hi·hi + hi·lo + lo·hi), spelled
out so that it computes the same on any backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def normalized_adjacency(num_nodes: int, edges: np.ndarray) -> np.ndarray:
    """Dense Ã = (D+I)^-1/2 (A+I) (D+I)^-1/2."""
    a = np.zeros((num_nodes, num_nodes), dtype=np.float32)
    a[edges[:, 0], edges[:, 1]] = 1.0
    a[edges[:, 1], edges[:, 0]] = 1.0
    np.fill_diagonal(a, 0.0)
    d = 1.0 / np.sqrt(a.sum(axis=1) + 1.0)
    a += np.eye(num_nodes, dtype=np.float32)
    return (a * d[:, None]) * d[None, :]


def activation(name: str):
    return {"relu": jax.nn.relu, "tanh": jnp.tanh,
            "identity": lambda x: x}[name]


def init_weights(dims, seed: int) -> list:
    """Glorot normal, one W_l per layer, split off one key per layer."""
    key = jax.random.key(seed)
    ws = []
    for l in range(len(dims) - 1):
        key, sub = jax.random.split(key)
        scale = jnp.sqrt(2.0 / (dims[l] + dims[l + 1]))
        ws.append(scale * jax.random.normal(sub, (dims[l], dims[l + 1]),
                                            dtype=jnp.float32))
    return ws


def matmul_highest(x, y):
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


def _bf16_split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def matmul_high(x, y):
    """float32 product in three bfloat16 passes, the low·low term left
    out: what ``Precision.HIGH`` computes on a TPU."""
    xh, xl = _bf16_split(x)
    yh, yl = _bf16_split(y)
    return (matmul_highest(xh, yh) + matmul_highest(xh, yl)
            + matmul_highest(xl, yh))


MATMULS = {"highest": matmul_highest, "high": matmul_high}


def forward(mm, f, a, z0, ws, reassociate: bool = False) -> list:
    zs, z = [], z0
    for l, w in enumerate(ws):
        z = mm(a, mm(z, w)) if reassociate else mm(mm(a, z), w)
        if l < len(ws) - 1:
            z = f(z)
        zs.append(z)
    return zs


def _nll(z, labels):
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def _mm_step(obj, x, t0, admm):
    """x − ∇obj/t, t doubled from t0/2 until obj(x⁺) ≤ obj(x) − ‖∇‖²/2t
    (up to the relative slack), at most ``max_backtracks`` times."""
    growth = admm["backtrack_growth"]
    val, grad = jax.value_and_grad(obj)(x)
    g_sq = jnp.sum(grad * grad)

    def accepted(t):
        bound = val - 0.5 * g_sq / t
        tol = admm["backtrack_rtol"] * (jnp.abs(bound) + 1e-12)
        return bound + tol >= obj(x - grad / t)

    def cond(c):
        t, it = c
        return (~accepted(t)) & (it < admm["max_backtracks"])

    t0 = jnp.maximum(t0 / growth, 1e-8)
    t, _ = jax.lax.while_loop(cond, lambda c: (c[0] * growth, c[1] + 1),
                              (t0, jnp.asarray(0)))
    return x - grad / t, t


def _fista(obj, z_init, admm):
    grad_fn = jax.grad(obj)
    growth = admm["backtrack_growth"]

    def step(carry, _):
        z, y, t, lip = carry
        val_y, g = obj(y), grad_fn(y)
        g_sq = jnp.sum(g * g)

        def accepted(lip):
            bound = val_y - 0.5 * g_sq / lip
            tol = admm["backtrack_rtol"] * (jnp.abs(bound) + 1e-12)
            return obj(y - g / lip) <= bound + tol

        def cond(c):
            lip, it = c
            return (~accepted(lip)) & (it < admm["max_backtracks"])

        lip, _ = jax.lax.while_loop(
            cond, lambda c: (c[0] * growth, c[1] + 1), (lip, jnp.asarray(0)))
        z_new = y - g / lip
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = z_new + ((t - 1.0) / t_new) * (z_new - z)
        return (z_new, y_new, t_new, lip * 0.9), None

    init = (z_init, z_init, jnp.asarray(1.0), jnp.asarray(admm["rho"] + 1.0))
    (z, _, _, _), _ = jax.lax.scan(step, init, None,
                                   length=admm["fista_iters"])
    return z


class Reference:
    """The reference for one graph, configuration and community split.

    ``reassociate_init`` starts it from Z_l = Ã (Z_{l-1} W_l) in place of
    (Ã Z_{l-1}) W_l: the same point in exact arithmetic, another at float32
    rounding, as a rewrite that changes only the order of summation
    reaches.  The readings tool compares it with the reference."""

    def __init__(self, config: dict, g, communities: np.ndarray,
                 precision: str | None = None,
                 reassociate_init: bool = False):
        self.mm = MATMULS[precision or config["precision"]["matmul"]]
        self.dims = list(config["model"]["layer_dims"])
        self.admm = dict(config["admm"])
        self.f = activation(config["model"]["activation"])
        n = g.num_nodes
        a = normalized_adjacency(n, g.edges)
        comm = np.asarray(communities)
        m = int(comm.max()) + 1
        self.lanes = [np.flatnonzero(comm == c) for c in range(m)]
        # neighbour communities of each community (Ã block non-zero), self
        # included; lane m's coupling term runs over their rows only
        blk = np.zeros((m, m), dtype=bool)
        blk[comm[g.edges[:, 0]], comm[g.edges[:, 1]]] = True
        blk |= blk.T
        np.fill_diagonal(blk, True)
        self.nbr_rows = [blk[c][comm].astype(np.float32) for c in range(m)]
        self.a = jnp.asarray(a)
        self.a_cols = [jnp.asarray(a[:, rows]) for rows in self.lanes]
        del a
        self.z0 = jnp.asarray(g.features)
        self.labels = jnp.asarray(g.labels.astype(np.int32))
        self.train = jnp.asarray(g.train_mask.astype(np.float32))
        self.denom = float(g.train_mask.sum())
        self._step = jax.jit(self._round)
        self._lag = jax.jit(self._lagrangian)
        self._init = jax.jit(partial(forward, self.mm, self.f,
                                     reassociate=reassociate_init))

    # -- state ---------------------------------------------------------
    def initial(self, seed: int) -> dict:
        ws = init_weights(self.dims, seed)
        zs = self._init(self.a, self.z0, ws)
        n_l = len(ws)
        return {"w": list(ws), "z": list(zs), "u": jnp.zeros_like(zs[-1]),
                "tau": [jnp.asarray(self.admm["tau_init"])] * n_l,
                "theta": [jnp.full((len(self.lanes),), self.admm["tau_init"])
                          for _ in range(n_l)]}

    def step(self, state: dict) -> dict:
        return self._step(self.a, self.a_cols, self.z0, self.labels,
                          self.train, state)

    def lagrangian(self, state: dict) -> float:
        return float(self._lag(self.a, self.z0, self.labels, self.train,
                               state))

    # -- one round of Algorithm 1 --------------------------------------
    def _round(self, a, a_cols, z0, labels, train, st):
        admm, f, mm = self.admm, self.f, self.mm
        nu, rho = admm["nu"], admm["rho"]
        ws, zs, u = st["w"], st["z"], st["u"]
        n_l = len(ws)
        ins = [z0] + zs[:-1]

        # W update, every layer from Z^k (Jacobi over layers)
        new_ws, new_taus = [], []
        for l in range(n_l):
            agg = mm(a, ins[l])
            if l < n_l - 1:
                def obj(w, agg=agg, z=zs[l]):
                    r = z - f(mm(agg, w))
                    return 0.5 * nu * jnp.sum(r * r)
            else:
                def obj(w, agg=agg, z=zs[l]):
                    r = z - mm(agg, w)
                    return jnp.sum(u * r) + 0.5 * rho * jnp.sum(r * r)
            w_new, tau = _mm_step(obj, ws[l], st["tau"][l], admm)
            new_ws.append(w_new)
            new_taus.append(tau)

        # Z_l, l < L: one step per community on ψ_{l,m}, others at Z^k
        new_zs, new_thetas = [], []
        for l in range(1, n_l):
            target = f(mm(mm(a, ins[l - 1]), new_ws[l - 1]))
            w_next = new_ws[l]
            base = mm(mm(a, zs[l - 1]), w_next)      # Ã Z_l^k W_{l+1}
            z_next = zs[l]
            lane_z, lane_t = [], []
            for c, rows in enumerate(self.lanes):
                wt = self.nbr_rows[c][:, None]
                zk = zs[l - 1][rows]

                def psi(z, rows=rows, c=c, zk=zk, wt=wt, l=l, target=target,
                        base=base, z_next=z_next, w_next=w_next):
                    r1 = z - target[rows]
                    v1 = 0.5 * nu * jnp.sum(r1 * r1)
                    pre = base + mm(a_cols[c], mm(z - zk, w_next))
                    if l + 1 < n_l:
                        r2 = (z_next - f(pre)) * wt
                        return v1 + 0.5 * nu * jnp.sum(r2 * r2)
                    r2 = (z_next - pre) * wt
                    return v1 + jnp.sum(u * r2) + 0.5 * rho * jnp.sum(r2 * r2)
                z_new, theta = _mm_step(psi, zk, st["theta"][l - 1][c], admm)
                lane_z.append((rows, z_new))
                lane_t.append(theta)
            z_out = zs[l - 1]
            for rows, z_new in lane_z:
                z_out = z_out.at[rows].set(z_new)
            new_zs.append(z_out)
            new_thetas.append(jnp.stack(lane_t))

        # Z_L: per-community FISTA on eq. (7)
        b = mm(mm(a, ins[-1]), new_ws[-1])
        z_last = zs[-1]
        for rows in self.lanes:
            b_m, u_m, y_m, t_m = b[rows], u[rows], labels[rows], train[rows]

            def obj(z, b_m=b_m, u_m=u_m, y_m=y_m, t_m=t_m):
                r = z - b_m
                ce = jnp.sum(_nll(z, y_m) * t_m) / self.denom
                return ce + jnp.sum(u_m * r) + 0.5 * rho * jnp.sum(r * r)
            z_last = z_last.at[rows].set(_fista(obj, zs[-1][rows], admm))
        new_zs.append(z_last)
        new_thetas.append(st["theta"][-1])

        # U: dual ascent on the new iterates
        z_pen = new_zs[-2] if n_l >= 2 else z0
        new_u = u + rho * (new_zs[-1] - mm(mm(a, z_pen), new_ws[-1]))
        return {"w": new_ws, "z": new_zs, "u": new_u, "tau": new_taus,
                "theta": new_thetas}

    def _lagrangian(self, a, z0, labels, train, st):
        """ℒ_ρ(W, Z, U), eq. (1)."""
        admm, f, mm = self.admm, self.f, self.mm
        ws, zs, u = st["w"], st["z"], st["u"]
        val = jnp.sum(_nll(zs[-1], labels) * train) / self.denom
        z_prev = z0
        for l in range(len(ws) - 1):
            r = zs[l] - f(mm(mm(a, z_prev), ws[l]))
            val += 0.5 * admm["nu"] * jnp.sum(r * r)
            z_prev = zs[l]
        r = zs[-1] - mm(mm(a, z_prev), ws[-1])
        return val + jnp.sum(u * r) + 0.5 * admm["rho"] * jnp.sum(r * r)


def host(state: dict) -> dict:
    """W, Z and U of a reference state on the host."""
    return {"w": [np.asarray(w) for w in state["w"]],
            "z": [np.asarray(z) for z in state["z"]],
            "u": np.asarray(state["u"]),
            "tau": [float(t) for t in state["tau"]]}
