"""The value-agnostic hybrid scan operator (paper Section III).

A hybrid scan is an index scan over the fully-indexed page prefix
stitched to a table scan over the remainder:

1. Range-scan the partial index; re-check the full predicate and MVCC
   visibility on the fetched rows (index keys may be stale after
   updates -- the table is the source of truth).
2. Track rho_m = largest page id containing an index-scan match, and
   rho_i = largest fully indexed page id (= built_pages - 1).
3. Start the table scan at  start_page = max(rho_m, rho_i + 1).
4. Deduplicate the overlapping page: index matches on pages
   >= start_page are dropped (they are re-discovered by the table
   scan).  This realises the paper's sorted-structure dedup with a
   single vectorised mask.

Exactly-once correctness relies on the in-order build invariant of
``index.build_pages_vap``: entries beyond the built prefix exist only
for the single in-progress page, so rho_m <= rho_i + 1 and every page
is covered by exactly one of the two sub-scans (modulo the dedup on
the overlapping page).  Property tests (tests/test_hybrid_scan.py)
verify completeness and exactly-once against a brute-force oracle,
including mid-build states, updates, and inserts.

Masked stitch (coverage-bitmap generalization)
----------------------------------------------
``*_masked`` variants take a per-page ``covered`` bool mask (from
``index.PageCoverage``) instead of relying on the prefix watermark:
the index side keeps matches on covered pages only
(``idx_keep = idx_match & covered[pg]``), the table side scans exactly
the uncovered pages (``~covered``), and the two page sets partition
the table -- exactly-once holds for ANY bitmap as long as set bits
mean fully-indexed pages (the PageCoverage hard invariant).  For a
bitmap that is a pure prefix of length L, ``covered[pg]`` equals
``pg < L`` and ``~covered`` equals ``page_ids >= L``, so every mask,
sum, and accounting value is bit-identical to the legacy stitch at
``start_page = L``.  ``prefix_len`` (host-computed) is reported as
``start_page`` purely for accounting continuity.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.index import AdHocIndex, index_range_scan, key_range
from repro.core.table import Table, conj_predicate_mask, visible_mask


class BatchScanResult(NamedTuple):
    """Per-query aggregates + accounting from one batched execution.

    Every field is (n_queries,); entry q is what the family's
    per-query body (``_hybrid_scan_core``, ``_masked_scan_core`` or
    the vmapped ``one``) returns for query q's bounds and snapshot.
    A single statement runs as a batch of one.
    """

    agg_sum: jax.Array  # (B,) int32
    count: jax.Array  # (B,) int32
    pages_scanned: jax.Array  # (B,) int32
    entries_probed: jax.Array  # (B,) int32
    start_page: jax.Array  # (B,) int32


def _predicate_key_bounds(key_attrs: tuple, attrs: tuple, los, his):
    """Packed-key range implied by a conjunctive predicate for an index
    keyed on ``key_attrs``.  Requires the index's leading attribute to
    appear in the predicate; missing trailing attributes widen to the
    full domain."""
    pmap = {a: k for k, a in enumerate(attrs)}
    if key_attrs[0] not in pmap:
        raise ValueError(
            "index leading attribute not constrained by predicate"
        )
    lo0, hi0 = los[pmap[key_attrs[0]]], his[pmap[key_attrs[0]]]
    if len(key_attrs) == 1:
        return key_range(lo0, hi0)
    if key_attrs[1] in pmap:
        lo1, hi1 = los[pmap[key_attrs[1]]], his[pmap[key_attrs[1]]]
    else:
        lo1, hi1 = -(2**31), 2**31 - 1
    return key_range(lo0, hi0, lo1, hi1)


def _hybrid_scan_core(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    ts,
    agg_attr: int,
):
    """Shared hybrid-scan body: returns the aggregate/accounting tuple
    plus the match masks (the exactly-once tests read them).  The
    batched path vmaps this and drops the masks (XLA prunes the dead
    mask computation after jit)."""
    psz = table.page_size
    lo_key, hi_key = _predicate_key_bounds(key_attrs, attrs, los, his)

    # ---- 1. index scan -------------------------------------------------
    entry_mask, rids = index_range_scan(index, lo_key, hi_key)
    pg = rids // psz
    sl = rids % psz
    rows_ok = conj_predicate_mask(table, attrs, los, his)[pg, sl]
    rows_ok &= visible_mask(table, ts)[pg, sl]
    idx_match = entry_mask & rows_ok  # (capacity,)

    # ---- 2. rho_m / rho_i ----------------------------------------------
    rho_m = jnp.max(jnp.where(idx_match, pg, -1))
    rho_i = index.built_pages - 1

    # ---- 3. stitch point -----------------------------------------------
    start_page = jnp.maximum(rho_m, rho_i + 1)

    # ---- 4. dedup + combine --------------------------------------------
    idx_keep = idx_match & (pg < start_page)
    page_ids = jnp.arange(table.n_pages, dtype=jnp.int32)[:, None]
    tbl_mask = conj_predicate_mask(table, attrs, los, his)
    tbl_mask &= visible_mask(table, ts)
    tbl_mask &= page_ids >= start_page

    vals = table.data[:, :, agg_attr]
    idx_sum = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
    tbl_sum = jnp.sum(jnp.where(tbl_mask, vals, 0), dtype=jnp.int32)
    count = jnp.sum(idx_keep, dtype=jnp.int32)
    count = count + jnp.sum(tbl_mask, dtype=jnp.int32)

    # Cost accounting: only pages up to the append watermark are real;
    # reserved headroom pages beyond it hold no tuples and a real
    # engine would never read them.
    used_pages = (table.n_rows + psz - 1) // psz
    pages_scanned = jnp.clip(used_pages - start_page, 0, None)
    entries_probed = jnp.sum(entry_mask, dtype=jnp.int32)
    stats = (
        idx_sum + tbl_sum,
        count,
        pages_scanned.astype(jnp.int32),
        entries_probed,
        start_page.astype(jnp.int32),
    )
    return stats, idx_keep, tbl_mask, pg, sl


# ---------------------------------------------------------------------------
# Batched multi-query scans (the executor's read-burst substrate)
# ---------------------------------------------------------------------------
# Each takes per-query bounds ``los``/``his`` of shape (B, len(attrs))
# and snapshots ``tss`` of shape (B,) and evaluates every query in ONE
# jitted dispatch over shared table state -- the launch-amortisation
# analogue of the Pallas kernel in kernels/batched_filter_agg.py
# (which the ops layer exposes for TPU deployments; on CPU these
# vmapped forms are the fast path).  A single statement runs as a
# batch of one.


@functools.partial(jax.jit, static_argnames=("attrs", "agg_attr"))
def batched_full_table_scan(
    table: Table, attrs: tuple, los, his, tss, agg_attr: int
) -> BatchScanResult:
    """B plain table scans in one dispatch."""

    def one(lo, hi, ts):
        tbl_mask = conj_predicate_mask(table, attrs, lo, hi)
        tbl_mask &= visible_mask(table, ts)
        vals = table.data[:, :, agg_attr]
        s = jnp.sum(jnp.where(tbl_mask, vals, 0), dtype=jnp.int32)
        c = jnp.sum(tbl_mask, dtype=jnp.int32)
        used = (table.n_rows + table.page_size - 1) // table.page_size
        z = jnp.zeros((), jnp.int32)
        return s, c, used.astype(jnp.int32), z, z

    return BatchScanResult(*jax.vmap(one)(los, his, tss))


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def batched_hybrid_scan(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    """B hybrid scans over one shared partial index in one dispatch.
    Per-query stitch points (start_page) fall out of the vmapped core."""

    def one(lo, hi, ts):
        stats, *_ = _hybrid_scan_core(
            table, index, key_attrs, attrs, lo, hi, ts, agg_attr
        )
        return stats

    return BatchScanResult(*jax.vmap(one)(los, his, tss))


def _masked_scan_core(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    ts,
    agg_attr: int,
    covered,
    prefix_len,
):
    """Shared masked-stitch body (see module docstring): ``covered`` is
    a (n_pages,) bool mask of fully-indexed pages, ``prefix_len`` the
    host-computed leading-run length reported as ``start_page``."""
    psz = table.page_size
    lo_key, hi_key = _predicate_key_bounds(key_attrs, attrs, los, his)

    entry_mask, rids = index_range_scan(index, lo_key, hi_key)
    pg = rids // psz
    sl = rids % psz
    rows_ok = conj_predicate_mask(table, attrs, los, his)[pg, sl]
    rows_ok &= visible_mask(table, ts)[pg, sl]
    idx_match = entry_mask & rows_ok

    # Partition by the bitmap: covered pages answer from the index,
    # uncovered pages are table scanned -- no rho, no dedup window.
    idx_keep = idx_match & covered[pg]
    tbl_mask = conj_predicate_mask(table, attrs, los, his)
    tbl_mask &= visible_mask(table, ts)
    tbl_mask &= ~covered[:, None]

    vals = table.data[:, :, agg_attr]
    idx_sum = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
    tbl_sum = jnp.sum(jnp.where(tbl_mask, vals, 0), dtype=jnp.int32)
    count = jnp.sum(idx_keep, dtype=jnp.int32)
    count = count + jnp.sum(tbl_mask, dtype=jnp.int32)

    used_pages = (table.n_rows + psz - 1) // psz
    page_ids = jnp.arange(table.n_pages, dtype=jnp.int32)
    pages_scanned = jnp.sum(~covered & (page_ids < used_pages),
                            dtype=jnp.int32)
    entries_probed = jnp.sum(entry_mask, dtype=jnp.int32)
    stats = (
        idx_sum + tbl_sum,
        count,
        pages_scanned,
        entries_probed,
        jnp.asarray(prefix_len, jnp.int32),
    )
    return stats, idx_keep, tbl_mask, pg, sl


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def batched_hybrid_scan_masked(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    covered,
    prefix_len,
) -> BatchScanResult:
    """B bitmap-stitched hybrid scans in one dispatch (the coverage
    mask is shared -- it is index state, not query state)."""

    def one(lo, hi, ts):
        stats, *_ = _masked_scan_core(
            table, index, key_attrs, attrs, lo, hi, ts, agg_attr,
            covered, prefix_len
        )
        return stats

    return BatchScanResult(*jax.vmap(one)(los, his, tss))


class HybridPrefixResult(NamedTuple):
    """Per-query index-prefix portion of a batched hybrid scan.

    The companion of the Pallas kernel's per-query ``start_pages``
    table suffix (``ops.scan_table_batched``): ``agg_sum``/``count``
    cover only deduplicated index matches on pages < ``start_page``;
    adding the kernel's suffix aggregates reconstructs the full hybrid
    result bit-identically to ``batched_hybrid_scan``.
    """

    agg_sum: jax.Array  # (B,) int32
    count: jax.Array  # (B,) int32
    entries_probed: jax.Array  # (B,) int32
    start_page: jax.Array  # (B,) int32


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def batched_hybrid_index_prefix(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> HybridPrefixResult:
    """B hybrid-scan index prefixes + stitch points in one dispatch."""
    psz = table.page_size
    vals = table.data[:, :, agg_attr]

    def one(lo, hi, ts):
        lo_key, hi_key = _predicate_key_bounds(key_attrs, attrs, lo, hi)
        entry_mask, rids = index_range_scan(index, lo_key, hi_key)
        pg, sl = rids // psz, rids % psz
        rows_ok = conj_predicate_mask(table, attrs, lo, hi)[pg, sl]
        rows_ok &= visible_mask(table, ts)[pg, sl]
        idx_match = entry_mask & rows_ok
        rho_m = jnp.max(jnp.where(idx_match, pg, -1))
        start_page = jnp.maximum(rho_m, index.built_pages)  # rho_i + 1
        idx_keep = idx_match & (pg < start_page)
        s = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
        c = jnp.sum(idx_keep, dtype=jnp.int32)
        return (
            s,
            c,
            jnp.sum(entry_mask, dtype=jnp.int32),
            start_page.astype(jnp.int32),
        )

    return HybridPrefixResult(*jax.vmap(one)(los, his, tss))


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def batched_masked_index_side(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    covered,
    prefix_len,
) -> HybridPrefixResult:
    """Index side of B masked hybrid scans: the companion of the
    masked Pallas table suffix (``ops.scan_table_batched_masked``),
    exactly as ``batched_hybrid_index_prefix`` companions the
    ``start_pages`` suffix.  Adding the kernel's uncovered-page
    aggregates reconstructs ``batched_hybrid_scan_masked`` bit for
    bit (int32 addition is associative)."""
    psz = table.page_size
    vals = table.data[:, :, agg_attr]

    def one(lo, hi, ts):
        lo_key, hi_key = _predicate_key_bounds(key_attrs, attrs, lo, hi)
        entry_mask, rids = index_range_scan(index, lo_key, hi_key)
        pg, sl = rids // psz, rids % psz
        rows_ok = conj_predicate_mask(table, attrs, lo, hi)[pg, sl]
        rows_ok &= visible_mask(table, ts)[pg, sl]
        idx_keep = entry_mask & rows_ok & covered[pg]
        s = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
        c = jnp.sum(idx_keep, dtype=jnp.int32)
        return (
            s,
            c,
            jnp.sum(entry_mask, dtype=jnp.int32),
            jnp.asarray(prefix_len, jnp.int32),
        )

    return HybridPrefixResult(*jax.vmap(one)(los, his, tss))


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def batched_pure_index_scan(
    table: Table,
    index: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    """B index-only scans in one dispatch -- legal only when the index
    covers the predicate (FULL scheme with a complete index, or VBP
    with a covered sub-domain)."""
    psz = table.page_size

    def one(lo, hi, ts):
        lo_key, hi_key = _predicate_key_bounds(key_attrs, attrs, lo, hi)
        entry_mask, rids = index_range_scan(index, lo_key, hi_key)
        pg, sl = rids // psz, rids % psz
        rows_ok = conj_predicate_mask(table, attrs, lo, hi)[pg, sl]
        rows_ok &= visible_mask(table, ts)[pg, sl]
        idx_match = entry_mask & rows_ok
        vals = table.data[:, :, agg_attr]
        s = jnp.sum(jnp.where(idx_match, vals[pg, sl], 0), dtype=jnp.int32)
        c = jnp.sum(idx_match, dtype=jnp.int32)
        return (
            s,
            c,
            jnp.zeros((), jnp.int32),
            jnp.sum(entry_mask, dtype=jnp.int32),
            jnp.asarray(table.n_pages, jnp.int32),
        )

    return BatchScanResult(*jax.vmap(one)(los, his, tss))
