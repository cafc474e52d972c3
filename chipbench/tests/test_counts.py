"""Operation and compulsory-byte counts against hand counts on tiny shapes."""
import pytest

from chipbench.lib import counts, peaks


def matmul(m, k, n):
    return 2 * m * k * n


def test_gcn_epoch_flops_by_hand():
    # n=4 nodes, Â with 6 stored entries, 3 features, hidden 2, 2 classes
    n, nnz, f, h, c = 4, 6, 3, 2, 2
    hand = (matmul(n, f, h) * 2          # X W1 and dW1 = Xᵀ dZ
            + 2 * nnz * h * 2            # Â (X W1) and Âᵀ dY
            + matmul(n, h, c) * 3        # H W2, dW2, dH
            + 2 * nnz * c * 2)           # Â (H W2) and Âᵀ dY
    assert hand == 288
    assert counts.gcn_epoch_flops(n, nnz, f, h, c) == hand


def test_spmm_compulsory_by_hand():
    flops, nbytes = counts.spmm_compulsory(4, 4, 6, 2)
    assert flops == 2 * 6 * 2
    # 6 entries x (4 B index + 4 B value) + 5 row pointers x 4 B
    # + operand 4 x 2 x 4 B + output 4 x 2 x 4 B
    assert nbytes == 48 + 20 + 32 + 32


def test_gcn_calls_are_the_four_aggregations():
    calls = counts.gcn_epoch_spmm_calls(4, 6, 2, 3)
    assert [f for f, _ in calls] == [24, 36, 36, 24]


def test_sage_step_flops_by_hand():
    # block 0 over the feature matrix: 3 real rows, 5 real edges, 4 -> 3;
    # block 1: 2 rows, 4 edges, 3 -> 2
    hand0 = 2 * 5 * 4 + 2 * matmul(3, 4, 3) * 2      # no input gradient
    hand1 = 2 * 4 * 3 * 2 + 2 * matmul(2, 3, 2) * 3
    assert (hand0, hand1) == (328, 192)
    assert counts.sage_step_flops([(3, 5), (2, 4)], [4, 3, 2]) == hand0 + hand1


def test_least_time_takes_the_binding_peak():
    p = peaks.peaks_for("TPU v5 lite")
    assert counts.least_time(197e12, 1.0, p) == pytest.approx(1.0)
    assert counts.least_time(1.0, 819e9, p) == pytest.approx(1.0)
