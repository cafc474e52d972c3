"""GNN models: tuned-vs-baseline accuracy parity (the paper's claim),
learning above chance, per-arch smoke."""
import jax
import numpy as np
import pytest

from repro.core.patch import patched
from repro.data import make_dataset
from repro.models.gnn import GNN_ARCHS, build_bundle, make_gnn
from repro.train import train_gnn


@pytest.fixture(scope="module")
def ds():
    return make_dataset("reddit", scale=1 / 512, seed=2)


@pytest.fixture(scope="module")
def bundle(ds):
    return build_bundle(ds, k_hint=64, tune=True, slot_perm=True)


@pytest.mark.parametrize("arch", GNN_ARCHS)
def test_logits_parity_tuned_vs_baseline(ds, bundle, arch):
    """Same params, same inputs: patched and unpatched paths must emit the
    same logits (fp tolerance) — 'iSpLib does not alter the results'."""
    init, apply = make_gnn(arch, ds.num_features, 32, ds.num_classes)
    params = init(jax.random.PRNGKey(0))
    with patched(True):
        lt = apply(params, bundle, ds.x)
    with patched(False):
        lb = apply(params, bundle, ds.x)
    np.testing.assert_allclose(np.asarray(lt), np.asarray(lb), rtol=1e-3,
                               atol=2e-3)


@pytest.mark.parametrize("arch,lr,epochs", [("gcn", 1e-2, 40),
                                            ("sage-mean", 1e-2, 40),
                                            ("gin", 1e-3, 120)])
def test_training_learns(ds, arch, lr, epochs):
    res = train_gnn(arch, ds, hidden=64, epochs=epochs, lr=lr,
                    use_isplib=True)
    chance = 1.0 / ds.num_classes
    assert res.losses[-1] < res.losses[0], "loss must decrease"
    assert res.train_acc > 3 * chance, (res.train_acc, chance)


def test_tuned_and_baseline_same_accuracy(ds):
    r_t = train_gnn("gcn", ds, hidden=64, epochs=15, use_isplib=True, seed=3)
    r_b = train_gnn("gcn", ds, hidden=64, epochs=15, use_isplib=False, seed=3)
    assert abs(r_t.train_acc - r_b.train_acc) < 0.02
    np.testing.assert_allclose(r_t.losses, r_b.losses, rtol=2e-2, atol=2e-2)


def test_all_archs_smoke(ds, bundle):
    for arch in GNN_ARCHS:
        init, apply = make_gnn(arch, ds.num_features, 16, ds.num_classes)
        params = init(jax.random.PRNGKey(1))
        with patched(True):
            out = apply(params, bundle, ds.x)
        assert out.shape == (ds.num_nodes, ds.num_classes)
        assert bool(np.isfinite(np.asarray(out)).all()), arch
