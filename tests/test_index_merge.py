"""The index merge is bit-identical to the full lexsort it replaced.

A build step whose chunk is small beside the index sorts only its new
entries and merges them into the already-sorted index arrays; a larger
chunk is sorted with the index.  The reference here is the formula the
builds used before: concatenate the index with the new entries,
``jnp.lexsort`` all of it, keep the first ``capacity``.  Every slot is
compared, padding and rids included, on both sides of the size
threshold (``_ranks_chunk``).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import (AdHocIndex, _merge_entries, _ranks_chunk,
                              build_pages_at, build_pages_vap, make_index)
from repro.core.table import INF_TS, load_table, update_rows

I32_MAX = 2**31 - 1


def lexsort_reference(kh, kl, rids, capacity):
    order = jnp.lexsort((kl, kh))[:capacity]
    return kh[order], kl[order], rids[order]


def sorted_index(rng, capacity, n_old, domain, two_attr, rid_base=0):
    kh = np.full(capacity, I32_MAX, np.int32)
    kl = np.full(capacity, I32_MAX, np.int32)
    kh[:n_old] = rng.integers(0, domain, n_old)
    kl[:n_old] = rng.integers(0, domain, n_old) if two_attr else 0
    order = np.lexsort((kl, kh))
    rids = rid_base + rng.permutation(capacity).astype(np.int32)
    return kh[order], kl[order], rids


# name: (capacity, n_old, m, domain, two_attr, invalid share, old rid
# base).  The first cases rank their chunk into the index, the
# ``sorted_*`` ones are large enough to be sorted with it.
MERGE_CASES = {
    "ties": (4096, 3000, 200, 3, False, 0.0, 0),
    "one_attr": (4096, 2000, 256, 1000, False, 0.1, 0),
    "two_attr": (4096, 2000, 256, 6, True, 0.1, 0),
    "invalid_new": (4096, 1500, 300, 5, True, 0.5, 0),
    "empty_index": (4096, 0, 300, 4, True, 0.2, 0),
    "fills_capacity": (4096, 3840, 256, 4, True, 0.0, 0),
    "overflows_capacity": (4096, 3950, 256, 4, True, 0.0, 0),
    "old_rids_above_new": (4096, 2000, 256, 3, True, 0.2, 10_000),
    "sorted_ties": (257, 120, 90, 3, False, 0.0, 0),
    "sorted_invalid_new": (200, 90, 100, 5, True, 0.5, 0),
    "sorted_overflows_capacity": (160, 120, 64, 4, True, 0.0, 0),
    "sorted_chunk_of_capacity": (300, 150, 300, 6, True, 0.1, 10_000),
}


@pytest.mark.parametrize("case", list(MERGE_CASES))
def test_merge_matches_lexsort(case):
    capacity, n_old, m, domain, two_attr, invalid, rid_base = \
        MERGE_CASES[case]
    assert _ranks_chunk(m, capacity) != case.startswith("sorted_")
    rng = np.random.default_rng(len(case))
    okh, okl, orid = sorted_index(rng, capacity, n_old, domain, two_attr,
                                  rid_base)
    nkh = rng.integers(0, domain, m).astype(np.int32)
    nkl = (rng.integers(0, domain, m) if two_attr
           else np.zeros(m)).astype(np.int32)
    bad = rng.random(m) < invalid
    nkh[bad] = I32_MAX
    nkl[bad] = I32_MAX
    nrid = rng.integers(0, 5_000, m).astype(np.int32)
    old = tuple(jnp.asarray(a) for a in (okh, okl, orid))
    new = tuple(jnp.asarray(a) for a in (nkh, nkl, nrid))
    got = _merge_entries(old, new)
    want = lexsort_reference(
        *(jnp.concatenate(pair) for pair in zip(old, new)), capacity)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# Whole builds against the gather-and-sort build they replaced
# ---------------------------------------------------------------------------

def gather_sort_build(index, table, key_attrs, pages, page_ok):
    """The former build body: gather whole pages, lexsort everything."""
    psz = table.page_size
    pages_c = jnp.clip(pages, 0, table.n_pages - 1)
    rows = table.data[pages_c]
    cols = [rows[:, :, a] for a in key_attrs]
    kh = cols[0]
    kl = cols[1] if len(cols) == 2 else jnp.zeros_like(kh)
    kh, kl = kh.reshape(-1), kl.reshape(-1)
    rids = (pages_c[:, None] * psz
            + jnp.arange(psz, dtype=jnp.int32)[None, :]).reshape(-1)
    valid = ((table.begin_ts[pages_c] < INF_TS)
             & page_ok[:, None]).reshape(-1)
    kh = jnp.where(valid, kh, I32_MAX)
    kl = jnp.where(valid, kl, I32_MAX)
    mh, ml, mr = lexsort_reference(
        jnp.concatenate([index.key_hi, kh]),
        jnp.concatenate([index.key_lo, kl]),
        jnp.concatenate([index.rids, rids.astype(jnp.int32)]),
        index.capacity)
    return mh, ml, mr, index.n_entries + jnp.sum(valid, dtype=jnp.int32)


def reference_vap(index, table, key_attrs, n):
    start = int(index.built_pages)
    full = int(table.n_rows) // table.page_size
    pages = jnp.arange(start, start + n, dtype=jnp.int32)
    mh, ml, mr, n_entries = gather_sort_build(
        index, table, key_attrs, pages, pages < full)
    built = max(min(start + n, full), start)
    return AdHocIndex(mh, ml, mr, n_entries, jnp.asarray(built, jnp.int32))


def assert_same_index(got, want):
    for name in AdHocIndex._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)


def small_table(rng, rows, n_pages, psz=8, n_attrs=4, domain=5):
    vals = rng.integers(0, domain, size=(rows, n_attrs)).astype(np.int32)
    return load_table(vals, page_size=psz, n_pages=n_pages)


# name: (rows, n_pages, page size, quanta) -- each quantum is a page
# count.  The ``ranked_*`` tables are large enough beside a 64-page
# quantum for it to rank its chunk into the index; on the others every
# quantum is sorted with the index, as is any of more than 64 pages.
VAP_CASES = {
    # 8 rows a page: 45 full pages and a partial watermark page.
    "partial_watermark": (365, 70, 8, [3, 20, 64, 64]),
    # 40 pages, below the 64-page bucket: the bucket is the table.
    "table_below_bucket": (320, 40, 8, [5, 30, 64]),
    # A full 80-page table: the last quantum runs past its last page,
    # and the slice start is clamped to 80 - 64.
    "past_last_page": (640, 80, 8, [50, 40]),
    # 4 rows a page: 1000 full pages and a partial watermark page,
    # which the last quantum reaches.
    "ranked_partial_watermark": (4001, 1100, 4, [3, 20, 900, 64, 64]),
    # A full 1100-page table; the last quantum is clamped to 1036.
    "ranked_past_last_page": (4400, 1100, 4, [1000, 64, 64]),
}


@pytest.mark.parametrize("key_attrs", [(1,), (2, 3)], ids=["one", "two"])
@pytest.mark.parametrize(
    "case", list(VAP_CASES) + ["dead_versions", "ranked_dead_versions"])
def test_vap_build_matches_gather_and_sort(case, key_attrs):
    rng = np.random.default_rng(7)
    if case.endswith("dead_versions"):
        ranked = case.startswith("ranked_")
        psz, n_pages = (4, 1100) if ranked else (8, 90)
        table = small_table(rng, 400, n_pages, psz=psz)
        # UPDATEs terminate matching versions and append new ones, so
        # the built pages hold dead versions (indexed all the same).
        for ts, lo in ((1, 0), (2, 3)):
            table, _ = update_rows(table, (1,), jnp.array([lo]),
                                   jnp.array([lo]), jnp.array([2]),
                                   jnp.array([4]), ts, max_new=64)
        assert int((np.asarray(table.end_ts) < INF_TS).sum()) > 0
        quanta = [7, 33, 64]
    else:
        rows, n_pages, psz, quanta = VAP_CASES[case]
        table = small_table(rng, rows, n_pages, psz=psz)
    assert (_ranks_chunk(64 * table.page_size, table.capacity)
            == case.startswith("ranked_"))
    index = make_index(table.capacity)
    for n in quanta:
        want = reference_vap(index, table, key_attrs, n)
        index = build_pages_vap(index, table, key_attrs, pages_per_cycle=n)
        assert_same_index(index, want)
    assert int(index.n_entries) > 0


@pytest.mark.parametrize("key_attrs", [(1,), (2, 3)], ids=["one", "two"])
def test_pages_at_matches_gather_and_sort(key_attrs):
    """Explicit page lists out of order: high pages first, so later
    merges bring rids below the index's own (the crack-on-scan and
    hot-range case), with -1 padding in the lists.  Lists of 4 pages
    rank their chunk into the index, lists of 8 are sorted with it."""
    rng = np.random.default_rng(11)
    table = small_table(rng, 600, 80)
    index = make_index(table.capacity)
    assert _ranks_chunk(4 * 8, table.capacity)
    assert not _ranks_chunk(8 * 8, table.capacity)
    for pages in ([60, 70, 65, -1], [3, 1, 2, 40, 41, -1, -1, -1],
                  [10, 11, 12, 13, 14, 15, 16, 17]):
        pages = jnp.asarray(pages, jnp.int32)
        mh, ml, mr, n_entries = gather_sort_build(
            index, table, key_attrs, pages, pages >= 0)
        want = AdHocIndex(mh, ml, mr, n_entries, index.built_pages)
        index = build_pages_at(index, table, key_attrs, pages,
                               max_pages=pages.shape[0])
        assert_same_index(index, want)
