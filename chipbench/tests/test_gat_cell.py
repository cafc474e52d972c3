"""The GAT cell: its counts against hand counts, one whole run at a tiny size
coming out correct, and the planted fault that leaves the attention out
coming out not correct."""
import pytest

from chipbench.lib import gat_counts
from chipbench.tests.tiny import run_tiny, tiny_cell, within


def matmul(m, k, n):
    return 2 * m * k * n


def test_gat_epoch_flops_by_hand():
    # n=4 nodes, 6 entries of A + I, 3 features; layers (3 -> 2x2 concat),
    # (4 -> 2x2 concat), (4 -> 3 heads of 2, averaged)
    n, nnz = 4, 6
    dims = gat_counts.layer_dims(3, [2, 2, 3], [2, 2, 2], [True, True, False])
    assert dims == [(3, 2, 2), (4, 2, 2), (4, 3, 2)]
    hand = (matmul(n, 3, 4) * 2 + 12 * n * 4 + 3 * 2 * nnz * 4
            + matmul(n, 4, 4) * 3 + 12 * n * 4 + 3 * 2 * nnz * 4
            + matmul(n, 4, 6) * 3 + 12 * n * 6 + 3 * 2 * nnz * 6)
    assert gat_counts.gat_epoch_flops(n, nnz, dims) == hand


def test_pass_compulsory_by_hand():
    flops, nbytes = gat_counts.pass_compulsory(4, 4, 6, 2, 8)
    assert flops == 2 * 6 * 8
    # 6 entries x (4 B index + 2 x 4 B values) + 5 row pointers x 4 B
    # + operand 4 x 8 x 4 B + output 4 x 8 x 4 B
    assert nbytes == 72 + 20 + 128 + 128


def test_epoch_calls():
    dims = [(3, 2, 2), (4, 3, 2)]
    assert len(gat_counts.gat_epoch_spmm_calls(4, 6, dims)) == 4
    assert len(gat_counts.gat_epoch_sddmm_calls(4, 6, dims)) == 2


def test_window_holds_the_checked_epochs():
    """At about 24 s an epoch a 10 s window would time one epoch after the
    first and return two losses; the check compares three."""
    from chipbench.drivers import fullbatch_gat as drv
    assert drv.window_epochs(10, 24.07) == drv.CHECK_STEPS
    assert drv.window_epochs(10, 0.5) == 21
    with pytest.raises(ValueError):
        drv.numbers([1.0, 1.0], [1.0, 1.0, 1.0])


@pytest.fixture
def sell_plan(monkeypatch):
    """The tuner's full-size pick (a SELL plan of A + I) at the tiny size,
    where BSR would fit and win."""
    import repro.core.cache as cache
    from repro.core.autotune import KernelPlan
    monkeypatch.setattr(cache, "autotune", lambda a, k, **kw: KernelPlan(
        kind="sell", sell_c=8, sell_sigma=0, k_hint=k))


def test_tiny_run_is_correct(sell_plan):
    res = run_tiny("gat-reddit.full")
    assert res["correct"], res["checks"]
    assert res["metrics"]["epoch_s"]["value"] > 0


def test_uniform_attention_is_not_correct(sell_plan):
    """The reference with every entry of a row weighed alike, in the
    program's place, fails the cell's limits on the same seed."""
    from chipbench.lib import cells
    cell = tiny_cell("gat-reddit.full")
    drv = cells.driver(cell)
    s = drv.Setup(cell, cells.ROOT)
    seed = 3000000019
    gaps = drv.numbers(s.reference(seed, uniform_attention=True),
                       s.reference(seed))
    assert not within(gaps, cell.limits), gaps
