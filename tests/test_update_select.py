"""An UPDATE selects its first ``max_new`` matches from per-page counts.

Contracts under test (core/table.py's ``first_matches``, ``update_rows``
and ``sharded_update_rows``):

* The selection is ``np.flatnonzero(mask)[:max_new]`` for every entry
  below ``min(n_match, max_new)``; every later entry is the capacity,
  one past the last slot.
* ``update_rows`` equals a plain numpy MVCC UPDATE on all three planes,
  the watermark and the count: matches past ``n_rows`` or terminated
  are never chosen, and entries past the matches leave every
  ``end_ts`` slot they do not select untouched, the last slot of a full
  table among them.
* ``sharded_update_rows`` is bit-identical to ``update_rows``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.table import (INF_TS, NEVER_TS, Table, first_matches,
                              shard_table, sharded_update_rows, unshard_table,
                              update_rows)

N_PAGES, PAGE_SIZE, N_ATTRS = 6, 8, 3
CAPACITY = N_PAGES * PAGE_SIZE
MAX_NEW = 16
TS = 5


def _pattern(rids):
    p = np.zeros(CAPACITY, bool)
    p[list(rids)] = True
    return p


# name -> (rows that satisfy the predicate, n_rows, terminated rids)
CASES = {
    "no_match": (_pattern([]), 40, []),
    "fewer_than_max_new": (_pattern([0, 9, 10, 23, 39]), 40, []),
    "exactly_max_new": (_pattern(range(3, 35, 2)), 40, []),
    "more_than_max_new": (_pattern(range(1, 40)), 40, [2, 17]),
    "one_page": (_pattern(range(16, 24)), 40, []),
    "last_page": (_pattern(range(40, 48)), 44, []),
    "only_past_n_rows": (_pattern(range(30, 48)), 30, []),
    "terminated_only": (_pattern([4, 5, 6]), 40, [4, 5, 6]),
    "full_table": (_pattern(range(CAPACITY)), CAPACITY, []),
    "full_table_last_slot": (_pattern([0, CAPACITY - 1]), CAPACITY, []),
    "random_sparse": (np.random.default_rng(1).random(CAPACITY) < 0.1, 45,
                      [3, 7]),
    "random_dense": (np.random.default_rng(2).random(CAPACITY) < 0.7, 41,
                     list(range(0, 40, 5))),
}


def make_case(name):
    """numpy planes of the case: a_0 is 1 on the predicate's rows, rows
    below ``n_rows`` are live from ts 0, terminated ones ended at ts 2."""
    pattern, n_rows, dead = CASES[name]
    rng = np.random.default_rng(7)
    data = rng.integers(2, 100, (CAPACITY, N_ATTRS)).astype(np.int32)
    data[:, 0] = pattern
    begin = np.full(CAPACITY, NEVER_TS, np.int32)
    begin[:n_rows] = 0
    end = np.full(CAPACITY, INF_TS, np.int32)
    end[dead] = 2
    return data, begin, end, n_rows


def as_table(data, begin, end, n_rows):
    return Table(jnp.asarray(data.reshape(N_PAGES, PAGE_SIZE, N_ATTRS)),
                 jnp.asarray(begin.reshape(N_PAGES, PAGE_SIZE)),
                 jnp.asarray(end.reshape(N_PAGES, PAGE_SIZE)),
                 jnp.asarray(n_rows, jnp.int32))


def reference_update(data, begin, end, n_rows, set_attrs, set_vals):
    """Plain numpy MVCC UPDATE of the rows with a_0 == 1 visible at TS."""
    data, begin, end = data.copy(), begin.copy(), end.copy()
    match = (data[:, 0] == 1) & (begin <= TS) & (TS < end)
    rids = np.flatnonzero(match)[:MAX_NEW]
    end[rids] = TS
    new = data[rids]
    new[:, set_attrs] = set_vals
    slots = np.arange(n_rows, n_rows + len(rids))
    keep = slots < CAPACITY
    data[slots[keep]] = new[keep]
    begin[slots[keep]] = TS
    end[slots[keep]] = INF_TS
    return data, begin, end, min(n_rows + len(rids), CAPACITY), len(rids)


SET_ATTRS = np.asarray([2, 1], np.int32)
SET_VALS = np.asarray([-7, -8], np.int32)


def planes(t):
    return (np.asarray(t.data).reshape(CAPACITY, N_ATTRS),
            np.asarray(t.begin_ts).reshape(-1),
            np.asarray(t.end_ts).reshape(-1), int(t.n_rows))


@pytest.mark.parametrize("name", CASES)
def test_selection_is_the_first_matches_in_rid_order(name):
    data, begin, end, _ = make_case(name)
    match = (data[:, 0] == 1) & (begin <= TS) & (TS < end)
    rids, n_match = first_matches(
        jnp.asarray(match.reshape(N_PAGES, PAGE_SIZE)), MAX_NEW)
    want = np.flatnonzero(match)[:MAX_NEW]
    rids = np.asarray(rids)
    assert int(n_match) == match.sum()
    np.testing.assert_array_equal(rids[:len(want)], want)
    assert (rids[len(want):] == CAPACITY).all()


@pytest.mark.parametrize("name", CASES)
def test_update_rows_equals_numpy_mvcc(name):
    data, begin, end, n_rows = make_case(name)
    table, n_upd = update_rows(
        as_table(data, begin, end, n_rows), (0,), jnp.asarray([1]),
        jnp.asarray([1]), jnp.asarray(SET_ATTRS), jnp.asarray(SET_VALS), TS,
        max_new=MAX_NEW)
    *want, want_upd = reference_update(data, begin, end, n_rows, SET_ATTRS,
                                       SET_VALS)
    got = planes(table)
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(g, w)
    assert got[3] == want[3]
    assert int(n_upd) == want_upd
    # end_ts moved only on the terminated versions and the appended rows.
    moved = np.flatnonzero(got[2] != end)
    match = (data[:, 0] == 1) & (begin <= TS) & (TS < end)
    chosen = np.flatnonzero(match)[:MAX_NEW]
    appended = np.arange(n_rows, min(n_rows + want_upd, CAPACITY))
    assert set(moved) <= set(chosen) | set(appended)


@pytest.mark.parametrize("name", ["fewer_than_max_new", "more_than_max_new",
                                  "last_page", "full_table_last_slot",
                                  "random_dense"])
def test_sharded_update_is_bit_identical(name):
    data, begin, end, n_rows = make_case(name)
    args = ((0,), jnp.asarray([1]), jnp.asarray([1]), jnp.asarray(SET_ATTRS),
            jnp.asarray(SET_VALS), TS)
    st, n_sharded = sharded_update_rows(
        shard_table(as_table(data, begin, end, n_rows), 4), *args,
        max_new=MAX_NEW)
    table, n_plain = update_rows(as_table(data, begin, end, n_rows), *args,
                                 max_new=MAX_NEW)
    for g, w in zip(planes(unshard_table(st)), planes(table)):
        np.testing.assert_array_equal(g, w)
    assert int(n_sharded) == int(n_plain)


@pytest.mark.parametrize("seed", range(4))
def test_selection_on_random_masks(seed):
    rng = np.random.default_rng(seed)
    n_pages = int(rng.integers(1, 40))
    psz = int(rng.choice([1, 8, 256]))
    max_new = int(rng.integers(1, 600))
    for p in (0.0, 0.002, 0.05, 0.5, 1.0):
        mask = rng.random((n_pages, psz)) < p
        rids, n_match = first_matches(jnp.asarray(mask), max_new)
        want = np.flatnonzero(mask)[:max_new]
        rids = np.asarray(rids)
        assert int(n_match) == mask.sum()
        np.testing.assert_array_equal(rids[:len(want)], want)
        assert (rids[len(want):] == mask.size).all()
