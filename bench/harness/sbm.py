"""The benchmark's graph generator: a stochastic block model with the
statistics of one row of arXiv:2112.09335 Table 2.

A copy of the program's ``graph.synthetic_sbm`` (same random stream, so
the same seed gives the same graph), kept with the benchmark so that the
input of every cell cannot change with the program.  The statistics come
from the configuration file, not from a table in the program.

Labels are drawn uniformly over the classes.  An edge joins two nodes of
one class ``in_out_ratio`` times likelier than two nodes of different
classes, with the probabilities set so that the expected degree is the
configuration's ``avg_degree``.  Features are Gaussian around one centre
per class, each row scaled to unit norm.  The first ``train`` nodes of a
random permutation are the training set, the next ``test`` the test set.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class SBMGraph:
    edges: np.ndarray        # (E, 2) int32, i < j, each edge once
    features: np.ndarray     # (N, C0) float32
    labels: np.ndarray       # (N,) int32
    train_mask: np.ndarray   # (N,) bool
    test_mask: np.ndarray    # (N,) bool
    num_classes: int

    @property
    def num_nodes(self) -> int:
        return int(self.features.shape[0])

    @property
    def nnz(self) -> int:
        """Nonzeros of Ã = (D+I)^-1/2 (A+I) (D+I)^-1/2: both directions of
        every edge, plus the diagonal."""
        return 2 * int(self.edges.shape[0]) + self.num_nodes


def generate(data: dict, seed: int = 0) -> SBMGraph:
    """``data``: the ``data`` group of a configuration file."""
    n, k, c0 = int(data["nodes"]), int(data["classes"]), int(data["features"])
    deg, ratio = float(data["avg_degree"]), float(data["in_out_ratio"])
    n_train, n_test = int(data["train"]), int(data["test"])
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, k, size=n).astype(np.int32)

    p_out = deg / (n * (ratio / k + (1 - 1 / k)))
    p_in = ratio * p_out
    same = labels[:, None] == labels[None, :]
    prob = np.where(same, p_in, p_out)
    upper = np.triu(rng.random((n, n)) < prob, k=1)
    del prob, same
    edges = np.argwhere(upper).astype(np.int32)
    del upper

    centers = rng.normal(0.0, 1.0, size=(k, c0)).astype(np.float32)
    feats = centers[labels] + rng.normal(0, 1.2, size=(n, c0)).astype(
        np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True) + 1e-8

    order = rng.permutation(n)
    train_mask = np.zeros(n, dtype=bool)
    test_mask = np.zeros(n, dtype=bool)
    train_mask[order[:n_train]] = True
    test_mask[order[n_train:n_train + n_test]] = True
    return SBMGraph(edges=edges, features=feats, labels=labels,
                    train_mask=train_mask, test_mask=test_mask,
                    num_classes=k)
