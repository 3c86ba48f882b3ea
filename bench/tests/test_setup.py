"""Set-up: the partition cache and the reference's starting point."""
import jax
import numpy as np
import pytest

from harness import reference, sbm, setup
from conftest import TINY_CONFIG, TINY_TRAFFIC

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def graph_and_part():
    g = sbm.generate(TINY_CONFIG["data"], seed=0)
    part, _ = setup.partition("tiny-sbm", g, 3, "multilevel")
    return g, part


def test_partition_cache_hits_and_keys_on_edges(graph_and_part):
    g, part = graph_and_part
    again, hit = setup.partition("tiny-sbm", g, 3, "multilevel")
    assert hit
    np.testing.assert_array_equal(part, again)
    other = sbm.generate(dict(TINY_CONFIG["data"], avg_degree=6.0), seed=0)
    assert setup.partition_key("tiny-sbm", other, 3, "multilevel") != \
        setup.partition_key("tiny-sbm", g, 3, "multilevel")
    assert setup.partition_key("tiny-sbm", g, 4, "multilevel") != \
        setup.partition_key("tiny-sbm", g, 3, "multilevel")


def test_reference_starts_where_the_program_starts(graph_and_part):
    g, part = graph_and_part
    with jax.default_matmul_precision("highest"):
        tr = setup.build_trainer(TINY_CONFIG, TINY_TRAFFIC, g, part, 1, SEED)
        prog = setup.host_state(tr)
        ref = reference.host(
            reference.Reference(TINY_CONFIG, g, part).initial(SEED))
    for a, b in zip(prog["w"], ref["w"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(prog["z"] + [prog["u"]], ref["z"] + [ref["u"]]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
