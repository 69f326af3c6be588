"""Property tests for the value-agnostic hybrid scan (paper Section III).

The operator's contract: every tuple version visible at the snapshot
and matching the predicate is returned EXACTLY ONCE, regardless of how
much of the index is built, interleaved with MVCC updates/inserts.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core.hybrid_scan import _hybrid_scan_core
from repro.core.index import build_pages_vap, make_index
from repro.core.table import load_table, update_rows
from repro.kernels.ref import filter_agg_ref

PAGE = 8
ATTRS = 4
I32_MIN, I32_MAX = -(2**31), 2**31 - 1


@functools.partial(jax.jit, static_argnames=("agg_attr",))
def hybrid(t, idx, lo, hi, ts, agg_attr=2):
    """The hybrid body on key and predicate a_1: its (sum, count,
    pages_scanned, entries_probed, start_page) and the times each row
    was counted, from the index side's and the table side's masks."""
    stats, idx_keep, tbl_mask, pg, sl = _hybrid_scan_core(
        t, idx, (1,), (1,), jnp.array([lo]), jnp.array([hi]), ts,
        agg_attr)
    contrib = jnp.zeros(tbl_mask.shape, jnp.int32)
    contrib = contrib.at[pg, sl].add(idx_keep.astype(jnp.int32))
    return stats, contrib + tbl_mask.astype(jnp.int32)


def oracle(tbl, ts, lo, hi, attr=1, agg=2):
    data = np.asarray(tbl.data).reshape(-1, ATTRS)
    b = np.asarray(tbl.begin_ts).reshape(-1)
    e = np.asarray(tbl.end_ts).reshape(-1)
    vis = (b <= ts) & (ts < e)
    m = vis & (data[:, attr] >= lo) & (data[:, attr] <= hi)
    return int(data[m][:, agg].astype(np.int64).sum() & 0xFFFFFFFF), int(m.sum())


@settings(max_examples=60, deadline=None)
@given(
    n_rows=st.integers(5, 60),
    cycles=st.integers(0, 9),
    ppc=st.integers(1, 4),
    lo=st.integers(0, 80),
    width=st.integers(0, 60),
    seed=st.integers(0, 10_000),
    n_updates=st.integers(0, 3),
)
def test_exactly_once_and_complete(n_rows, cycles, ppc, lo, width, seed,
                                   n_updates):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 100, size=(n_rows, ATTRS)).astype(np.int32)
    n_pages = (n_rows + PAGE - 1) // PAGE + 3  # headroom for appends
    t = load_table(vals, page_size=PAGE, n_pages=n_pages, ts=0)
    idx = make_index(capacity=n_pages * PAGE)

    ts = 0
    for u in range(n_updates):
        ts += 5
        ulo = int(rng.integers(0, 80))
        t, _ = update_rows(t, (2,), jnp.array([ulo]), jnp.array([ulo + 20]),
                           jnp.array([3]), jnp.array([int(rng.integers(100))]),
                           ts, max_new=16)
        # interleave index building with updates
        idx = build_pages_vap(idx, t, key_attrs=(1,), pages_per_cycle=ppc)

    for _ in range(cycles):
        idx = build_pages_vap(idx, t, key_attrs=(1,), pages_per_cycle=ppc)

    snap = ts + 3
    (agg_sum, count, *_), contrib = hybrid(t, idx, lo, lo + width, snap)
    es, ec = oracle(t, snap, lo, lo + width)
    assert int(count) == ec, "completeness violated"
    assert int(np.asarray(agg_sum).astype(np.int64) & 0xFFFFFFFF) == es
    assert int(contrib.max()) <= 1, "tuple returned twice"
    # contributions match the oracle row set exactly
    data = np.asarray(t.data).reshape(-1, ATTRS)
    b = np.asarray(t.begin_ts).reshape(-1)
    e = np.asarray(t.end_ts).reshape(-1)
    vis = (b <= snap) & (snap < e)
    m = vis & (data[:, 1] >= lo) & (data[:, 1] <= lo + width)
    np.testing.assert_array_equal(np.asarray(contrib).reshape(-1) > 0, m)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), n_rows=st.integers(8, 40))
def test_start_page_formula(seed, n_rows):
    """start_page == max(rho_m, rho_i + 1), clipped to page count."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 50, size=(n_rows, ATTRS)).astype(np.int32)
    t = load_table(vals, page_size=PAGE)
    idx = make_index(capacity=t.capacity)
    idx = build_pages_vap(idx, t, key_attrs=(1,), pages_per_cycle=2)
    (*_, start_page), _contrib = hybrid(t, idx, 0, 50, 0)
    rho_i = int(idx.built_pages) - 1
    assert int(start_page) >= rho_i + 1


def test_matches_full_table_scan():
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 100, size=(50, ATTRS)).astype(np.int32)
    t = load_table(vals, page_size=PAGE)
    idx = make_index(capacity=t.capacity)
    idx = build_pages_vap(idx, t, key_attrs=(1,), pages_per_cycle=3)
    for lo, hi in [(0, 99), (20, 40), (90, 95), (50, 50)]:
        (agg_sum, count, *_), _contrib = hybrid(t, idx, lo, hi, 0)
        ref_sum, ref_count = filter_agg_ref(
            t.data[:, :, 1], t.data[:, :, 1], t.data[:, :, 2], t.begin_ts,
            t.end_ts, lo, hi, I32_MIN, I32_MAX, 0)
        assert int(count) == int(ref_count)
        assert int(agg_sum) == int(ref_sum)
