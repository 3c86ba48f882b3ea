"""The benchmark's copy of the SBM generator: pinned edge counts for the
two configurations, and the same stream as the program's generator."""
import numpy as np
import pytest

from harness import sbm, setup


@pytest.mark.parametrize("config, edges", [("gcn-amazon-photo", 119129),
                                           ("gcn-amazon-computers", 246294)])
def test_seed_0_edge_counts(config, edges):
    c = setup.read_json(setup.BENCH / "configs" / f"{config}.json")
    g = sbm.generate(c["data"], seed=c["data"]["generator_seed"])
    assert g.edges.shape == (edges, 2)
    assert g.num_nodes == c["data"]["nodes"]
    assert g.features.shape[1] == c["data"]["features"]
    assert int(g.train_mask.sum()) == c["data"]["train"]
    assert int(g.test_mask.sum()) == c["data"]["test"]
    assert not np.any(g.train_mask & g.test_mask)
    assert g.nnz == 2 * edges + c["data"]["nodes"]


def test_same_graph_as_the_programs_generator():
    setup.use_program()
    from repro.core import graph
    n, n_train, n_test, k, c0, deg = graph.DATASET_STATS["amazon_photo_mini"]
    data = {"nodes": n, "train": n_train, "test": n_test, "classes": k,
            "features": c0, "avg_degree": deg, "in_out_ratio": 12.0}
    ours = sbm.generate(data, seed=3)
    theirs = graph.synthetic_sbm("amazon_photo_mini", seed=3)
    for field in ("edges", "features", "labels", "train_mask", "test_mask"):
        np.testing.assert_array_equal(getattr(ours, field),
                                      getattr(theirs, field))
