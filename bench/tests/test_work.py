"""The yardstick's arithmetic: model FLOPs, the aggregation work a round
requires, the roofline bound and the table of peaks."""
import pytest

from harness import work

PHOTO = {"n": 7650, "edges": 119129, "dims": (745, 1000, 8)}
COMPUTERS = {"n": 13752, "edges": 246294, "dims": (767, 1000, 10)}


def nnz(c):
    return 2 * c["edges"] + c["n"]


@pytest.mark.parametrize("case, gflop", [(PHOTO, 35.67), (COMPUTERS, 66.47)])
def test_model_flops_match_the_hand_count(case, gflop):
    got = work.model_flops_per_round(case["n"], nnz(case), case["dims"])
    assert got / 1e9 == pytest.approx(gflop, abs=0.005)


def test_model_flops_by_hand_for_one_layer():
    # one layer 4 -> 2 over 3 nodes and 5 nonzeros: 3 × (2·5·2 + 2·3·4·2)
    assert work.model_flops_per_round(3, 5, (4, 2)) == 3 * (20 + 48)


def test_aggregations_of_a_two_layer_round():
    widths = [w for _, w in work.aggregations((745, 1000, 8))]
    # Ã Z0 and Ã Z1 for the W updates, the Z1 coupling's gradient and
    # value, and U's Ã Z1⁺ W2⁺
    assert widths == [745, 1000, 8, 8, 8]


def test_aggregation_cost_counts_nonzeros_indices_and_both_sides():
    flops, bytes_ = work.aggregation_cost(n=10, nnz=30, width=4)
    assert flops == 2 * 30 * 4
    assert bytes_ == 30 * 8 + 2 * 10 * 4 * 4


def test_least_time_takes_the_larger_bound_per_aggregation():
    peak = {"flops": 1e12, "bytes": 1e9}
    n, z = 100, 500
    expect = 0.0
    for w in (16, 32, 4, 4, 4):
        f, b = work.aggregation_cost(n, z, w)
        expect += max(f / 1e12, b / 1e9)
    got = work.aggregation_least_s(n, z, (16, 32, 4), peak)
    assert got == pytest.approx(expect)
    # memory-bound here: bytes over the bandwidth set every term
    assert got == pytest.approx(sum(
        work.aggregation_cost(n, z, w)[1] / 1e9 for w in (16, 32, 4, 4, 4)))


def test_photo_round_needs_about_0_14_ms_of_aggregation_on_v5e():
    least = work.aggregation_least_s(PHOTO["n"], nnz(PHOTO), PHOTO["dims"],
                                     work.peaks("TPU v5 lite"))
    assert 1.3e-4 < least < 1.6e-4


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
