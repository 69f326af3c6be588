"""Unit + property tests for the tuner's ML components: Holt-Winters
forecaster, CART classifier, 0-1 knapsack, VBP index semantics."""
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import forecaster as hw
from repro.core import knapsack
from repro.core.classifier import (READ_INTENSIVE, UNKNOWN, WRITE_INTENSIVE,
                                   CartClassifier, default_classifier,
                                   default_training_set)
from repro.core.index import (build_pages_vap, key_range, make_index,
                              make_vbp, vbp_populate_subdomain)
from repro.core.hybrid_scan import batched_pure_index_scan
from repro.core.table import load_table


# ---------------------------------------------------------------------------
# Holt-Winters
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(0.0, 1e5), min_size=1, max_size=40),
       st.integers(2, 8))
def test_hw_matches_reference(ys, m):
    st_ = hw.init_state(m)
    fcs = []
    for y in ys:
        st_ = hw.update(st_, y)
        fcs.append(float(hw.forecast(st_, 1)))
    _, ref_fcs = hw.ref_holt_winters(np.asarray(ys), m)
    np.testing.assert_allclose(fcs, ref_fcs, rtol=2e-4, atol=1e-4)


def test_hw_learns_trend():
    st_ = hw.init_state(4)
    for t in range(40):
        st_ = hw.update(st_, 10.0 * t)
    f = float(hw.forecast(st_, 1))
    assert 320 < f < 480, f  # next value ~400, trend captured


def test_hw_learns_seasonality():
    st_ = hw.init_state(8)
    pattern = [100, 100, 5, 5, 5, 5, 100, 100]
    for rep in range(12):
        for y in pattern:
            st_ = hw.update(st_, y)
    # after many seasons, the 1-step forecast at a 'high' slot is high
    f = float(hw.forecast(st_, 1))
    assert f > 40, f


def test_hw_batched_update():
    states = hw.init_state(4, batch=3)
    ys = jnp.asarray([1.0, 10.0, 100.0])
    states = hw.update_batch(states, ys, 0.5, 0.3, 0.4)
    f = hw.forecast_batch(states, 1)
    assert f.shape == (3,)
    assert float(f[2]) > float(f[0])


# ---------------------------------------------------------------------------
# CART classifier
# ---------------------------------------------------------------------------

def test_cart_separates_synthetic_workloads():
    X, y = default_training_set(512, seed=1)
    clf = CartClassifier().fit(X, y)
    acc = (clf.predict_batch(X) == y).mean()
    assert acc > 0.95, acc
    # the paper's key feature: scan/mutator ratio drives the root split
    assert clf.tree.feature[0] == 0


def test_cart_abstains_on_thin_snapshots():
    clf = default_classifier()
    assert clf.predict(np.array([5.0, 0.1, 100.0]), n_samples=2) == UNKNOWN
    lab = clf.predict(np.array([30.0, 0.1, 5000.0]), n_samples=100)
    assert lab == READ_INTENSIVE
    lab = clf.predict(np.array([0.2, 0.9, 30.0]), n_samples=100)
    assert lab == WRITE_INTENSIVE


def test_cart_describe_is_readable():
    clf = default_classifier()
    text = clf.describe()
    assert "scan_mutator_ratio" in text and "INTENSIVE" in text


# ---------------------------------------------------------------------------
# Knapsack
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.floats(0.0, 100.0), st.floats(0.1, 50.0)),
                min_size=1, max_size=10),
       st.floats(1.0, 120.0))
def test_knapsack_feasible_and_near_optimal(items, budget):
    utils = np.array([u for u, _ in items])
    sizes = np.array([s for _, s in items])
    keep = knapsack.solve(utils, sizes, budget, resolution=1024)
    assert sizes[keep].sum() <= budget * 1.01
    _, best = knapsack.brute_force(utils, sizes, budget)
    got = utils[keep].sum()
    # discretisation slack: within 10% of optimal (and never infeasible)
    assert got >= best * 0.90 - 1e-9


def test_knapsack_force_keep():
    utils = np.array([1.0, 100.0, 50.0])
    sizes = np.array([10.0, 10.0, 10.0])
    keep = knapsack.solve(utils, sizes, budget=15.0,
                          force_keep=np.array([True, False, False]))
    assert keep[0]
    assert sizes[keep].sum() <= 20.0 + 1e-9  # forced item may exceed alone


# ---------------------------------------------------------------------------
# VBP semantics
# ---------------------------------------------------------------------------

def test_vbp_overlapping_populates_never_duplicate():
    rng = np.random.default_rng(3)
    vals = rng.integers(0, 100, size=(64, 4)).astype(np.int32)
    t = load_table(vals, page_size=8)
    vbp = make_vbp(capacity=t.capacity)
    for lo, hi in [(10, 40), (30, 60), (0, 50), (45, 80)]:
        klo, khi = key_range(lo, hi)
        vbp, _ = vbp_populate_subdomain(vbp, t, (1,), klo, khi, 0,
                                        max_add=t.capacity)
        n = int(vbp.index.n_entries)
        rids = np.asarray(vbp.index.rids)[:n]
        assert len(np.unique(rids)) == n, "an index entry is duplicated"
        r = batched_pure_index_scan(t, vbp.index, (1,), (1,),
                                    jnp.array([[lo]]), jnp.array([[hi]]),
                                    jnp.zeros(1, jnp.int32), 2)
        m = (vals[:, 1] >= lo) & (vals[:, 1] <= hi)
        assert int(r.count[0]) == int(m.sum())


def test_vap_never_indexes_partial_watermark_page():
    rng = np.random.default_rng(4)
    vals = rng.integers(0, 100, size=(19, 4)).astype(np.int32)  # 2.4 pages
    t = load_table(vals, page_size=8, n_pages=4)
    idx = make_index(capacity=t.capacity)
    for _ in range(10):
        idx = build_pages_vap(idx, t, (1,), pages_per_cycle=2)
    # 19 rows / 8 per page -> only 2 FULL pages may ever be built
    assert int(idx.built_pages) == 2
