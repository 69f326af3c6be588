"""Scan engine: turns planned scans into jitted dispatches.

The execution half of the planner/engine split (the planner lives in
``core.planner``).  The engine knows nothing about catalogs or cost
models -- it receives an access path, raw index state and per-query
bounds, and owns the dispatch strategy:

* plain ``Table``   -- the batched operators of ``hybrid_scan``
  (vmapped jnp forms, or with ``use_kernel`` the multi-query Pallas
  kernel via ``kernels.ops``; the hybrid path stitches the kernel's
  per-query ``start_pages`` table suffix to the jnp index prefix).
* ``ShardedTable``  -- ONE dispatch regardless of shard count: the
  shards are stacked on a leading axis (``table.stacked_shards``, a
  cached padded pytree) and every batched scan family vmaps over that
  axis, so trace size, compile time and dispatch count stay flat as S
  grows.  With ``use_kernel`` the fused Pallas kernel runs the same
  layout as a (shard, page-block, query) grid with a per-(shard,
  query) scalar-prefetched ``start_pages`` table
  (``kernels.batched_filter_agg.sharded_batched_filter_agg``).  When
  the local devices can place the shard axis (``parallel.mesh``), the
  same stacked pytree rides a named mesh via ``shard_map`` -- the
  mesh forms below -- and every dispatch records its execution tier
  (``ScanEngine.last_tier``: single / vmap-stacked / kernel /
  shard_map) for the executor's telemetry.

Every scan, a single statement included, is a batch: one statement
runs as a batch of one through ``ScanEngine.scan_batch``, so each plan
family has one operator per tier.

Bit-identity contract (tests/test_sharded_engine.py): for any shard
count, every aggregate and accounting field equals the single-shard
value.  int32 sums wrap associatively/commutatively, so per-shard
partials reduce to the exact single-shard bit pattern in any order --
which is why the stacked forms' axis reductions agree bit for bit
with a per-shard loop over the shards tuple (the reference in
tests/test_fused_shard_scan.py), and why the padded shard grid is
safe: padding pages carry ``begin_ts == NEVER_TS``, are
invisible to every snapshot, and contribute exact int32 zeros.

The hybrid scan's cross-shard stitch works in two passes inside one
program: pass 1 probes each shard's local index and reduces the
per-query max global matched page (rho_m) across shards together with
the global built prefix (rho_i + 1 == sum of shard-local
``built_pages``); pass 2 re-walks each shard with the global stitch
point, deduplicating index matches and masking the table suffix
exactly like the single-table operator.  The per-shard stitch
(``hybrid_ps``) needs no cross-shard reduction at all -- see the
section note below.

Masked (bitmap) stitch: when an index's coverage is an arbitrary
built-page bitmap instead of a prefix (``core.index.PageCoverage``),
the same exactness argument holds with the partition rule
``covered[page]`` replacing every ``start_page`` comparison: index
hits on covered pages plus a table scan of exactly the uncovered
pages count each visible row exactly once.  The masked families need
NO cross-shard stitch reduction at all -- coverage is defined over
global page ids and each shard consumes its round-robin slice -- so
only the output sums cross shards, reduced by the same associative
int32 adds as every other family.  Accounting for the masked forms
(``pages_scanned``, the reported ``start_page``) is computed
host-side from the plan-pinned ``CoverageView`` (uncovered used
pages; the bitmap's leading built run), which reproduces the legacy
values bit-for-bit whenever the bitmap is a prefix -- the property
test in tests/test_coverage_bitmap.py pins that identity across
results AND accounting for 1 and 4 shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hybrid_scan import (
    BatchScanResult,
    _predicate_key_bounds,
    batched_full_table_scan,
    batched_hybrid_index_prefix,
    batched_hybrid_scan,
    batched_hybrid_scan_masked,
    batched_masked_index_side,
    batched_pure_index_scan,
)
from repro.core.index import (
    AdHocIndex,
    ShardedIndex,
    index_range_scan,
    stacked_shard_indexes,
)
from repro.core.table import (
    ShardedTable,
    StackedShards,
    Table,
    conj_predicate_mask,
    stacked_shards,
    visible_mask,
)
from repro.parallel.mesh import (
    SHARD_AXIS,
    batch_spec,
    make_scan_mesh,
    stacked_specs,
)

# vmap axis prefixes: map the leading shard axis of every leaf.
_TABLE_AXES = Table(0, 0, 0, 0)
_INDEX_AXES = AdHocIndex(0, 0, 0, 0, 0)


def _used_pages(st: ShardedTable) -> jax.Array:
    """Global pages at/below the append watermark (real pages; reserved
    headroom beyond it holds no tuples)."""
    return ((st.n_rows + st.page_size - 1) // st.page_size).astype(jnp.int32)


def _shard_index_probe(t, ix, s, S, key_attrs, attrs, lo, hi, ts):
    """Probe one shard's local index: masks, local page/slot of each
    entry, and this shard's contribution to the per-query rho_m (in
    *global* page ids).  ``s`` may be a Python int or a traced scalar
    (stacked and mesh fan-out); the arithmetic is identical."""
    psz = t.page_size
    lo_key, hi_key = _predicate_key_bounds(key_attrs, attrs, lo, hi)
    entry_mask, rids = index_range_scan(ix, lo_key, hi_key)
    pg, sl = rids // psz, rids % psz
    rows_ok = conj_predicate_mask(t, attrs, lo, hi)[pg, sl]
    rows_ok &= visible_mask(t, ts)[pg, sl]
    idx_match = entry_mask & rows_ok
    gpg = pg * S + s
    rho_m = jnp.max(jnp.where(idx_match, gpg, -1))
    return idx_match, gpg, pg, sl, entry_mask, rho_m


def _shard_table_mask(t, s, S, attrs, lo, hi, ts, start_page):
    """Predicate+visibility mask over one shard's pages whose *global*
    page id is >= the stitch point."""
    g_page_ids = (jnp.arange(t.n_pages, dtype=jnp.int32) * S + s)[:, None]
    mask = conj_predicate_mask(t, attrs, lo, hi) & visible_mask(t, ts)
    return mask & (g_page_ids >= start_page)


# ---------------------------------------------------------------------------
# Per-shard hybrid stitch (shard-aware tuning: relaxed prefix invariant)
# ---------------------------------------------------------------------------
#
# When build budget is routed per shard (or the table's layout is not
# round-robin), the union of shard-local built prefixes is no longer
# one global page prefix, so the global stitch point is meaningless --
# and, on skewed layouts, unsound.  The per-shard stitch needs no
# cross-shard reduction at all: each shard's entries are local, so the
# single-table stitch rule (start = max(rho_m, built), index prefix
# below it, table scan at/after it) applies shard by shard.  Aggregates
# stay bit-exact: the same rows are counted exactly once, only the
# *schedule* of which pages ride the index differs.  ``pages_scanned``
# sums the per-shard table suffixes; the reported ``start_page`` is the
# smallest global-equivalent stitch point (``lstart * S + s`` is the
# first table-scanned global page of shard s), which degenerates to the
# global stitch point whenever the prefixes are round-robin-consistent.


def _pershard_stitch(t, ix, s, S, key_attrs, attrs, lo, hi, ts):
    """One shard's local hybrid stitch: (idx_keep, pg, sl, entry_mask,
    tbl_mask, pages_suffix, global_equiv_start)."""
    idx_match, _gpg, pg, sl, entry_mask, _ = _shard_index_probe(
        t, ix, s, S, key_attrs, attrs, lo, hi, ts
    )
    lrho = jnp.max(jnp.where(idx_match, pg, -1))
    lstart = jnp.maximum(lrho, ix.built_pages)
    idx_keep = idx_match & (pg < lstart)
    page_ids = jnp.arange(t.n_pages, dtype=jnp.int32)[:, None]
    tbl_mask = conj_predicate_mask(t, attrs, lo, hi) & visible_mask(t, ts)
    tbl_mask &= page_ids >= lstart
    lused = ((t.n_rows + t.page_size - 1) // t.page_size).astype(jnp.int32)
    pages = jnp.clip(lused - lstart, 0, None).astype(jnp.int32)
    gstart = (lstart * S + s).astype(jnp.int32)
    return idx_keep, pg, sl, entry_mask, tbl_mask, pages, gstart


# ---------------------------------------------------------------------------
# Stacked batched scans: ONE dispatch for any shard count
# ---------------------------------------------------------------------------
#
# The read-burst fan-out.  Each family vmaps the per-shard body over
# the stacked pytree's leading shard axis instead of unrolling a
# Python loop, so the traced program -- and the compiled dispatch --
# is the same size for 1 shard and 64.  Padding pages (uniform page
# grid) are invisible (begin_ts == NEVER_TS) and padded index slots
# sit beyond ``n_entries``, so they add exact int32 zeros; axis
# reductions equal any other order of per-shard reductions bit-exactly
# because int32 add / max / min are associative and commutative.


def _shard_axis_map(fn, stk: StackedShards, six=None):
    """vmap ``fn`` over the leading shard axis (+ the shard id)."""
    if six is None:
        return jax.vmap(fn, in_axes=(_TABLE_AXES, 0))(
            stk.table, stk.shard_ids
        )
    return jax.vmap(fn, in_axes=(_TABLE_AXES, _INDEX_AXES, 0))(
        stk.table, six, stk.shard_ids
    )


def _sum0(x):
    return jnp.sum(x, axis=0, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("attrs", "agg_attr"))
def _stacked_batched_full(
    stk: StackedShards, n_rows, attrs: tuple, los, his, tss, agg_attr: int
) -> BatchScanResult:
    def shard(t, _s):
        def one(lo, hi, ts):
            mask = conj_predicate_mask(t, attrs, lo, hi) & visible_mask(t, ts)
            vals = t.data[:, :, agg_attr]
            return (
                jnp.sum(jnp.where(mask, vals, 0), dtype=jnp.int32),
                jnp.sum(mask, dtype=jnp.int32),
            )

        return jax.vmap(one)(los, his, tss)

    sums, cnts = _shard_axis_map(shard, stk)
    B = los.shape[0]
    psz = stk.table.data.shape[2]
    used = ((n_rows + psz - 1) // psz).astype(jnp.int32)
    z = jnp.zeros((B,), jnp.int32)
    return BatchScanResult(
        _sum0(sums), _sum0(cnts), jnp.full((B,), used, jnp.int32), z, z
    )


def _stacked_start_pages(stk, six, key_attrs, attrs, los, his, tss):
    """Pass 1 of the global stitch: per-query global stitch points."""
    S = stk.shard_ids.shape[0]

    def shard(t, ix, s):
        def one(lo, hi, ts):
            probe = _shard_index_probe(
                t, ix, s, S, key_attrs, attrs, lo, hi, ts
            )
            return probe[5]

        return jax.vmap(one)(los, his, tss)

    rho = _shard_axis_map(shard, stk, six)
    rho_m = jnp.max(rho, axis=0)
    built = jnp.sum(six.built_pages, dtype=jnp.int32)
    return jnp.maximum(rho_m, built)  # rho_i + 1


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def _stacked_batched_hybrid(
    stk: StackedShards,
    six: AdHocIndex,
    n_rows,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    S = stk.shard_ids.shape[0]
    start_pages = _stacked_start_pages(
        stk, six, key_attrs, attrs, los, his, tss
    )

    def shard(t, ix, s):
        def one(lo, hi, ts, sp):
            idx_match, gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                t, ix, s, S, key_attrs, attrs, lo, hi, ts
            )
            idx_keep = idx_match & (gpg < sp)
            tbl_mask = _shard_table_mask(t, s, S, attrs, lo, hi, ts, sp)
            vals = t.data[:, :, agg_attr]
            s_ = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
            s_ = s_ + jnp.sum(jnp.where(tbl_mask, vals, 0), dtype=jnp.int32)
            c_ = jnp.sum(idx_keep, dtype=jnp.int32)
            c_ = c_ + jnp.sum(tbl_mask, dtype=jnp.int32)
            return s_, c_, jnp.sum(entry_mask, dtype=jnp.int32)

        return jax.vmap(one)(los, his, tss, start_pages)

    sums, cnts, ents = _shard_axis_map(shard, stk, six)
    psz = stk.table.data.shape[2]
    used = ((n_rows + psz - 1) // psz).astype(jnp.int32)
    pages = jnp.clip(used - start_pages, 0, None).astype(jnp.int32)
    return BatchScanResult(
        _sum0(sums),
        _sum0(cnts),
        pages,
        _sum0(ents),
        start_pages.astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def _stacked_batched_hybrid_ps(
    stk: StackedShards,
    six: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    S = stk.shard_ids.shape[0]

    def shard(t, ix, s):
        def one(lo, hi, ts):
            idx_keep, pg, sl, entry_mask, tbl_mask, pages_s, gstart = (
                _pershard_stitch(t, ix, s, S, key_attrs, attrs, lo, hi, ts)
            )
            vals = t.data[:, :, agg_attr]
            s_ = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
            s_ = s_ + jnp.sum(jnp.where(tbl_mask, vals, 0), dtype=jnp.int32)
            c_ = jnp.sum(idx_keep, dtype=jnp.int32)
            c_ = c_ + jnp.sum(tbl_mask, dtype=jnp.int32)
            e_ = jnp.sum(entry_mask, dtype=jnp.int32)
            return s_, c_, e_, pages_s, gstart

        return jax.vmap(one)(los, his, tss)

    sums, cnts, ents, pages, gstarts = _shard_axis_map(shard, stk, six)
    return BatchScanResult(
        _sum0(sums),
        _sum0(cnts),
        _sum0(pages),
        _sum0(ents),
        jnp.min(gstarts, axis=0).astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def _stacked_batched_pure_index(
    stk: StackedShards,
    six: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    S = stk.shard_ids.shape[0]

    def shard(t, ix, s):
        def one(lo, hi, ts):
            idx_match, _gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                t, ix, s, S, key_attrs, attrs, lo, hi, ts
            )
            vals = t.data[:, :, agg_attr]
            match_vals = jnp.where(idx_match, vals[pg, sl], 0)
            return (
                jnp.sum(match_vals, dtype=jnp.int32),
                jnp.sum(idx_match, dtype=jnp.int32),
                jnp.sum(entry_mask, dtype=jnp.int32),
            )

        return jax.vmap(one)(los, his, tss)

    sums, cnts, ents = _shard_axis_map(shard, stk, six)
    B = los.shape[0]
    n_pages = jnp.sum(stk.local_pages)
    return BatchScanResult(
        _sum0(sums),
        _sum0(cnts),
        jnp.zeros((B,), jnp.int32),
        _sum0(ents),
        jnp.full((B,), n_pages, jnp.int32),
    )


@functools.partial(
    jax.jit,
    static_argnames=("key_attrs", "attrs", "agg_attr", "table_side"),
)
def _stacked_batched_masked(
    stk: StackedShards,
    six: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    covered,
    table_side: bool = True,
):
    """B masked stitches on the stacked shard axis in ONE dispatch:
    (sums, cnts, ents), each (B,).  ``covered`` is the (S, max_pages)
    local-page bitmap; with ``table_side=False`` only the index half
    runs (the fused-kernel pre-pass, companion of
    ``ops.scan_shards_batched_masked``)."""
    S = stk.shard_ids.shape[0]

    def shard(t, ix, s, lc):
        def one(lo, hi, ts):
            idx_match, _gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                t, ix, s, S, key_attrs, attrs, lo, hi, ts
            )
            idx_keep = idx_match & lc[pg]
            vals = t.data[:, :, agg_attr]
            s_ = jnp.sum(
                jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32
            )
            c_ = jnp.sum(idx_keep, dtype=jnp.int32)
            if table_side:
                tbl = conj_predicate_mask(t, attrs, lo, hi) & visible_mask(
                    t, ts
                )
                tbl &= (~lc)[:, None]
                s_ = s_ + jnp.sum(jnp.where(tbl, vals, 0), dtype=jnp.int32)
                c_ = c_ + jnp.sum(tbl, dtype=jnp.int32)
            return s_, c_, jnp.sum(entry_mask, dtype=jnp.int32)

        return jax.vmap(one)(los, his, tss)

    sums, cnts, ents = jax.vmap(
        shard, in_axes=(_TABLE_AXES, _INDEX_AXES, 0, 0)
    )(stk.table, six, stk.shard_ids, covered)
    return _sum0(sums), _sum0(cnts), _sum0(ents)


def _masked_batch_accounting(st, cov_view, B):
    """(pages_scanned, start_page) broadcast to the batch, host-derived
    from the pinned coverage view (see the module docstring)."""
    used = -(-int(st.n_rows) // st.page_size)
    pages = int((~cov_view.built_host[:used]).sum())
    return (
        jnp.full((B,), pages, jnp.int32),
        jnp.full((B,), cov_view.prefix_len, jnp.int32),
    )


def sharded_batched_hybrid_scan_masked(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    cov_view,
) -> BatchScanResult:
    """B masked hybrid scans in ONE dispatch (stacked fan-out)."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    sums, cnts, ents = _stacked_batched_masked(
        stk, six, key_attrs, attrs, los, his, tss, agg_attr, cov_view.mask
    )
    pages, starts = _masked_batch_accounting(st, cov_view, los.shape[0])
    return BatchScanResult(sums, cnts, pages, ents, starts)


# -- hybrid index prefixes for the fused-kernel table suffix ---------------


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def _stacked_hybrid_prefix(
    stk: StackedShards,
    six: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
):
    """Global-stitch index prefix + per-(shard, query) local start
    pages for the fused kernel: (sums, cnts, ents, start_pages (B,),
    local_starts (S, B))."""
    S = stk.shard_ids.shape[0]
    start_pages = _stacked_start_pages(
        stk, six, key_attrs, attrs, los, his, tss
    )

    def shard(t, ix, s):
        def one(lo, hi, ts, sp):
            idx_match, gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                t, ix, s, S, key_attrs, attrs, lo, hi, ts
            )
            idx_keep = idx_match & (gpg < sp)
            vals = t.data[:, :, agg_attr]
            s_ = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
            c_ = jnp.sum(idx_keep, dtype=jnp.int32)
            return s_, c_, jnp.sum(entry_mask, dtype=jnp.int32)

        return jax.vmap(one)(los, his, tss, start_pages)

    sums, cnts, ents = _shard_axis_map(shard, stk, six)
    # Local pages of shard s with global id < start:
    # ceil((start - s) / S), clipped at 0 (floor division rounds
    # toward -inf, so the +S-1 form is exact ceil for any sign).
    local = start_pages[None, :] - stk.shard_ids[:, None] + S - 1
    local_starts = jnp.maximum(local // S, 0).astype(jnp.int32)
    return (
        _sum0(sums),
        _sum0(cnts),
        _sum0(ents),
        start_pages.astype(jnp.int32),
        local_starts,
    )


@functools.partial(jax.jit, static_argnames=("key_attrs", "attrs", "agg_attr"))
def _stacked_hybrid_prefix_ps(
    stk: StackedShards,
    six: AdHocIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
):
    """Per-shard-stitch index prefix for the fused kernel:
    (sums, cnts, ents, local_starts (S, B), pages (B,), gstart (B,))."""
    S = stk.shard_ids.shape[0]

    def shard(t, ix, s):
        def one(lo, hi, ts):
            idx_keep, pg, sl, entry_mask, _tbl, pages_s, gstart = (
                _pershard_stitch(t, ix, s, S, key_attrs, attrs, lo, hi, ts)
            )
            vals = t.data[:, :, agg_attr]
            s_ = jnp.sum(jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32)
            c_ = jnp.sum(idx_keep, dtype=jnp.int32)
            e_ = jnp.sum(entry_mask, dtype=jnp.int32)
            return s_, c_, e_, gstart // S, pages_s, gstart

        return jax.vmap(one)(los, his, tss)

    sums, cnts, ents, lstarts, pages, gstarts = _shard_axis_map(
        shard, stk, six
    )
    return (
        _sum0(sums),
        _sum0(cnts),
        _sum0(ents),
        lstarts.astype(jnp.int32),
        _sum0(pages),
        jnp.min(gstarts, axis=0).astype(jnp.int32),
    )


# -- public single-dispatch entry points -----------------------------------


def sharded_batched_full_table_scan(
    st: ShardedTable, attrs: tuple, los, his, tss, agg_attr: int
) -> BatchScanResult:
    """B plain table scans over every shard in ONE dispatch."""
    stk = stacked_shards(st)
    return _stacked_batched_full(
        stk, st.n_rows, attrs, los, his, tss, agg_attr
    )


def sharded_batched_hybrid_scan(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    """B hybrid scans (global stitch) in ONE dispatch: the rho_m
    reduction, the global stitch point and both sub-scans all live on
    the stacked shard axis."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    return _stacked_batched_hybrid(
        stk, six, st.n_rows, key_attrs, attrs, los, his, tss, agg_attr
    )


def sharded_batched_hybrid_scan_pershard(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    """B hybrid scans with shard-local stitch points in ONE dispatch
    (no cross-shard reduction pass at all)."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    return _stacked_batched_hybrid_ps(
        stk, six, key_attrs, attrs, los, his, tss, agg_attr
    )


def sharded_batched_pure_index_scan(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
) -> BatchScanResult:
    """B index-only scans in ONE dispatch."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    return _stacked_batched_pure_index(
        stk, six, key_attrs, attrs, los, his, tss, agg_attr
    )


# ---------------------------------------------------------------------------
# Mesh-native fan-out (shard_map): the stacked shard axis bound to a
# named mesh axis; cross-shard reductions become axis collectives
# ---------------------------------------------------------------------------
#
# Each mapped body receives a contiguous *slice* of the stacked pytree
# (S_local = S / mesh_devices shards) and runs the same per-shard mask
# arithmetic as the stacked vmapped forms above; only the cross-shard
# reductions differ in spelling: the hybrid stitch's rho_m becomes
# ``jax.lax.pmax`` over the mesh axis, the built-prefix sum and every
# output accounting sum become ``psum``, and the per-shard stitch's
# global start page becomes ``pmin``.  ``hybrid_ps`` needs no stitch
# collective at all -- its stitch points are shard-local -- so only
# the output reductions touch the wire.  int32 add/max/min associate
# and commute, so every collective reduces to the exact bit pattern of
# the single-device axis-0 reduction regardless of device count.
#
# ``use_kernel`` swaps the table-suffix mask arithmetic for one Pallas
# kernel launch per locally-owned shard (the non-interpret TPU path
# with per-chip block shapes, ``kernels.batched_filter_agg``); the
# engine only selects it off-CPU (``kernels.ops.interpret_mode()``
# False), so CPU meshes keep the bit-identical jnp bodies.


def _mesh_kernel_suffix(stk, attrs, los, his, tss, agg_attr, starts):
    """Table-suffix partials for the local shard slice, one fused
    kernel launch per locally-owned shard.  ``starts`` is the
    per-(local shard, query) table of local stitch points (None for
    pure full scans)."""
    from repro.kernels import ops as _kops

    s_local = stk.shard_ids.shape[0]
    B = los.shape[0]
    sums = jnp.zeros((B,), jnp.int32)
    cnts = jnp.zeros((B,), jnp.int32)
    for i in range(s_local):
        t = jax.tree.map(lambda x: x[i], stk.table)
        sp = jnp.zeros((B,), jnp.int32) if starts is None else starts[i]
        s_, c_ = _kops.scan_table_batched(
            t, attrs, los, his, tss, agg_attr, start_pages=sp
        )
        sums, cnts = sums + s_, cnts + c_
    return sums, cnts


@functools.lru_cache(maxsize=64)
def _mesh_full_fn(mesh, attrs: tuple, agg_attr: int, use_kernel: bool):
    bspec = batch_spec(mesh)

    def body(stk, los, his, tss):
        if use_kernel:
            sums, cnts = _mesh_kernel_suffix(
                stk, attrs, los, his, tss, agg_attr, starts=None
            )
        else:

            def shard(t, _s):
                def one(lo, hi, ts):
                    mask = conj_predicate_mask(
                        t, attrs, lo, hi
                    ) & visible_mask(t, ts)
                    vals = t.data[:, :, agg_attr]
                    return (
                        jnp.sum(jnp.where(mask, vals, 0), dtype=jnp.int32),
                        jnp.sum(mask, dtype=jnp.int32),
                    )

                return jax.vmap(one)(los, his, tss)

            sums, cnts = _shard_axis_map(shard, stk)
            sums, cnts = _sum0(sums), _sum0(cnts)
        return (
            jax.lax.psum(sums, SHARD_AXIS),
            jax.lax.psum(cnts, SHARD_AXIS),
        )

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(stacked_specs(), bspec, bspec, bspec),
        out_specs=(bspec, bspec),
        check_vma=False,
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=64)
def _mesh_hybrid_fn(
    mesh,
    S: int,
    key_attrs: tuple,
    attrs: tuple,
    agg_attr: int,
    use_kernel: bool,
):
    """Global-stitch hybrid under shard_map: pass 1's rho_m reduction
    is a ``pmax`` over the mesh axis, so the global stitch point is
    replicated into every mapped body for pass 2."""
    bspec = batch_spec(mesh)

    def body(stk, six, los, his, tss):
        def shard1(t, ix, s):
            def one(lo, hi, ts):
                probe = _shard_index_probe(
                    t, ix, s, S, key_attrs, attrs, lo, hi, ts
                )
                return probe[5]

            return jax.vmap(one)(los, his, tss)

        rho = _shard_axis_map(shard1, stk, six)
        rho_m = jax.lax.pmax(jnp.max(rho, axis=0), SHARD_AXIS)
        built = jax.lax.psum(
            jnp.sum(six.built_pages, dtype=jnp.int32), SHARD_AXIS
        )
        start_pages = jnp.maximum(rho_m, built)  # rho_i + 1

        def shard2(t, ix, s):
            def one(lo, hi, ts, sp):
                idx_match, gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                    t, ix, s, S, key_attrs, attrs, lo, hi, ts
                )
                idx_keep = idx_match & (gpg < sp)
                vals = t.data[:, :, agg_attr]
                s_ = jnp.sum(
                    jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32
                )
                c_ = jnp.sum(idx_keep, dtype=jnp.int32)
                if not use_kernel:
                    tbl = _shard_table_mask(t, s, S, attrs, lo, hi, ts, sp)
                    s_ = s_ + jnp.sum(
                        jnp.where(tbl, vals, 0), dtype=jnp.int32
                    )
                    c_ = c_ + jnp.sum(tbl, dtype=jnp.int32)
                return s_, c_, jnp.sum(entry_mask, dtype=jnp.int32)

            return jax.vmap(one)(los, his, tss, start_pages)

        sums, cnts, ents = _shard_axis_map(shard2, stk, six)
        sums, cnts, ents = _sum0(sums), _sum0(cnts), _sum0(ents)
        if use_kernel:
            local = start_pages[None, :] - stk.shard_ids[:, None] + S - 1
            local_starts = jnp.maximum(local // S, 0).astype(jnp.int32)
            ks, kc = _mesh_kernel_suffix(
                stk, attrs, los, his, tss, agg_attr, local_starts
            )
            sums, cnts = sums + ks, cnts + kc
        return (
            jax.lax.psum(sums, SHARD_AXIS),
            jax.lax.psum(cnts, SHARD_AXIS),
            jax.lax.psum(ents, SHARD_AXIS),
            start_pages.astype(jnp.int32),
        )

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(stacked_specs(), stacked_specs(), bspec, bspec, bspec),
        out_specs=(bspec, bspec, bspec, bspec),
        check_vma=False,
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=64)
def _mesh_hybrid_ps_fn(
    mesh,
    S: int,
    key_attrs: tuple,
    attrs: tuple,
    agg_attr: int,
    use_kernel: bool,
):
    """Per-shard stitch under shard_map: NO stitch collective (the
    stitch points are shard-local by construction); only the output
    accounting reductions cross the mesh axis."""
    bspec = batch_spec(mesh)

    def body(stk, six, los, his, tss):
        def shard(t, ix, s):
            def one(lo, hi, ts):
                idx_keep, pg, sl, entry_mask, tbl_mask, pages_s, gstart = (
                    _pershard_stitch(
                        t, ix, s, S, key_attrs, attrs, lo, hi, ts
                    )
                )
                vals = t.data[:, :, agg_attr]
                s_ = jnp.sum(
                    jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32
                )
                c_ = jnp.sum(idx_keep, dtype=jnp.int32)
                if not use_kernel:
                    s_ = s_ + jnp.sum(
                        jnp.where(tbl_mask, vals, 0), dtype=jnp.int32
                    )
                    c_ = c_ + jnp.sum(tbl_mask, dtype=jnp.int32)
                e_ = jnp.sum(entry_mask, dtype=jnp.int32)
                return s_, c_, e_, pages_s, gstart

            return jax.vmap(one)(los, his, tss)

        sums, cnts, ents, pages, gstarts = _shard_axis_map(shard, stk, six)
        sums, cnts = _sum0(sums), _sum0(cnts)
        if use_kernel:
            ks, kc = _mesh_kernel_suffix(
                stk, attrs, los, his, tss, agg_attr, gstarts // S
            )
            sums, cnts = sums + ks, cnts + kc
        return (
            jax.lax.psum(sums, SHARD_AXIS),
            jax.lax.psum(cnts, SHARD_AXIS),
            jax.lax.psum(_sum0(pages), SHARD_AXIS),
            jax.lax.psum(_sum0(ents), SHARD_AXIS),
            jax.lax.pmin(
                jnp.min(gstarts, axis=0).astype(jnp.int32), SHARD_AXIS
            ),
        )

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(stacked_specs(), stacked_specs(), bspec, bspec, bspec),
        out_specs=(bspec, bspec, bspec, bspec, bspec),
        check_vma=False,
    )
    return jax.jit(mapped)


def _mesh_kernel_suffix_masked(stk, attrs, los, his, tss, agg_attr, words):
    """Masked table-suffix partials for the local shard slice: one
    masked kernel launch per locally-owned shard, each fed its own
    (1, W) slice of the packed coverage words."""
    from repro.kernels import ops as _kops

    s_local = stk.shard_ids.shape[0]
    B = los.shape[0]
    sums = jnp.zeros((B,), jnp.int32)
    cnts = jnp.zeros((B,), jnp.int32)
    for i in range(s_local):
        t = jax.tree.map(lambda x, i=i: x[i], stk.table)
        s_, c_ = _kops.scan_table_batched_masked(
            t, attrs, los, his, tss, agg_attr, words[i : i + 1]
        )
        sums, cnts = sums + s_, cnts + c_
    return sums, cnts


@functools.lru_cache(maxsize=64)
def _mesh_hybrid_masked_fn(
    mesh,
    S: int,
    key_attrs: tuple,
    attrs: tuple,
    agg_attr: int,
    use_kernel: bool,
):
    """Masked stitch under shard_map: NO stitch collective at all (the
    bitmap partitions pages shard-locally); only the output sums cross
    the mesh axis.  The (S, max_pages) bitmap and (S, W) packed words
    ride the same shard-axis placement as the stacked pytree."""
    bspec = batch_spec(mesh)

    def body(stk, six, covered, words, los, his, tss):
        def shard(t, ix, s, lc):
            def one(lo, hi, ts):
                idx_match, _gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                    t, ix, s, S, key_attrs, attrs, lo, hi, ts
                )
                idx_keep = idx_match & lc[pg]
                vals = t.data[:, :, agg_attr]
                s_ = jnp.sum(
                    jnp.where(idx_keep, vals[pg, sl], 0), dtype=jnp.int32
                )
                c_ = jnp.sum(idx_keep, dtype=jnp.int32)
                if not use_kernel:
                    tbl = conj_predicate_mask(
                        t, attrs, lo, hi
                    ) & visible_mask(t, ts)
                    tbl &= (~lc)[:, None]
                    s_ = s_ + jnp.sum(
                        jnp.where(tbl, vals, 0), dtype=jnp.int32
                    )
                    c_ = c_ + jnp.sum(tbl, dtype=jnp.int32)
                return s_, c_, jnp.sum(entry_mask, dtype=jnp.int32)

            return jax.vmap(one)(los, his, tss)

        sums, cnts, ents = jax.vmap(
            shard, in_axes=(_TABLE_AXES, _INDEX_AXES, 0, 0)
        )(stk.table, six, stk.shard_ids, covered)
        sums, cnts, ents = _sum0(sums), _sum0(cnts), _sum0(ents)
        if use_kernel:
            ks, kc = _mesh_kernel_suffix_masked(
                stk, attrs, los, his, tss, agg_attr, words
            )
            sums, cnts = sums + ks, cnts + kc
        return (
            jax.lax.psum(sums, SHARD_AXIS),
            jax.lax.psum(cnts, SHARD_AXIS),
            jax.lax.psum(ents, SHARD_AXIS),
        )

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stacked_specs(),
            stacked_specs(),
            stacked_specs(),
            stacked_specs(),
            bspec,
            bspec,
            bspec,
        ),
        out_specs=(bspec, bspec, bspec),
        check_vma=False,
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=64)
def _mesh_pure_index_fn(
    mesh, S: int, key_attrs: tuple, attrs: tuple, agg_attr: int
):
    bspec = batch_spec(mesh)

    def body(stk, six, los, his, tss):
        def shard(t, ix, s):
            def one(lo, hi, ts):
                idx_match, _gpg, pg, sl, entry_mask, _ = _shard_index_probe(
                    t, ix, s, S, key_attrs, attrs, lo, hi, ts
                )
                vals = t.data[:, :, agg_attr]
                match_vals = jnp.where(idx_match, vals[pg, sl], 0)
                return (
                    jnp.sum(match_vals, dtype=jnp.int32),
                    jnp.sum(idx_match, dtype=jnp.int32),
                    jnp.sum(entry_mask, dtype=jnp.int32),
                )

            return jax.vmap(one)(los, his, tss)

        sums, cnts, ents = _shard_axis_map(shard, stk, six)
        return (
            jax.lax.psum(_sum0(sums), SHARD_AXIS),
            jax.lax.psum(_sum0(cnts), SHARD_AXIS),
            jax.lax.psum(_sum0(ents), SHARD_AXIS),
        )

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(stacked_specs(), stacked_specs(), bspec, bspec, bspec),
        out_specs=(bspec, bspec, bspec),
        check_vma=False,
    )
    return jax.jit(mapped)


def mesh_batched_full_table_scan(
    st: ShardedTable,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    mesh,
    use_kernel: bool = False,
) -> BatchScanResult:
    """B plain table scans over every shard in ONE mesh dispatch."""
    stk = stacked_shards(st)
    fn = _mesh_full_fn(mesh, attrs, agg_attr, use_kernel)
    sums, cnts = fn(
        stk, jnp.asarray(los), jnp.asarray(his), jnp.asarray(tss)
    )
    B = los.shape[0]
    z = jnp.zeros((B,), jnp.int32)
    used = jnp.full((B,), _used_pages(st), jnp.int32)
    return BatchScanResult(sums, cnts, used, z, z)


def mesh_batched_hybrid_scan(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    mesh,
    use_kernel: bool = False,
) -> BatchScanResult:
    """B hybrid scans (global stitch) in ONE mesh dispatch: rho_m is a
    pmax over the mesh axis inside the mapped body."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    S = int(stk.shard_ids.shape[0])
    fn = _mesh_hybrid_fn(mesh, S, key_attrs, attrs, agg_attr, use_kernel)
    sums, cnts, ents, start = fn(
        stk, six, jnp.asarray(los), jnp.asarray(his), jnp.asarray(tss)
    )
    pages = jnp.clip(_used_pages(st) - start, 0, None).astype(jnp.int32)
    return BatchScanResult(sums, cnts, pages, ents, start)


def mesh_batched_hybrid_scan_pershard(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    mesh,
    use_kernel: bool = False,
) -> BatchScanResult:
    """B hybrid scans with shard-local stitch points in ONE mesh
    dispatch (no cross-shard stitch collective at all)."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    S = int(stk.shard_ids.shape[0])
    fn = _mesh_hybrid_ps_fn(mesh, S, key_attrs, attrs, agg_attr, use_kernel)
    sums, cnts, pages, ents, gstart = fn(
        stk, six, jnp.asarray(los), jnp.asarray(his), jnp.asarray(tss)
    )
    return BatchScanResult(sums, cnts, pages, ents, gstart)


def mesh_batched_hybrid_scan_masked(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    cov_view,
    mesh,
    use_kernel: bool = False,
) -> BatchScanResult:
    """B masked hybrid scans in ONE mesh dispatch; accounting is
    host-derived from the pinned view exactly like the stacked form."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    S = int(stk.shard_ids.shape[0])
    fn = _mesh_hybrid_masked_fn(
        mesh, S, key_attrs, attrs, agg_attr, use_kernel
    )
    sums, cnts, ents = fn(
        stk,
        six,
        cov_view.mask,
        cov_view.words,
        jnp.asarray(los),
        jnp.asarray(his),
        jnp.asarray(tss),
    )
    pages, starts = _masked_batch_accounting(st, cov_view, los.shape[0])
    return BatchScanResult(sums, cnts, pages, ents, starts)


def mesh_batched_pure_index_scan(
    st: ShardedTable,
    index: ShardedIndex,
    key_attrs: tuple,
    attrs: tuple,
    los,
    his,
    tss,
    agg_attr: int,
    mesh,
) -> BatchScanResult:
    """B index-only scans in ONE mesh dispatch."""
    stk = stacked_shards(st)
    six = stacked_shard_indexes(index)
    S = int(stk.shard_ids.shape[0])
    fn = _mesh_pure_index_fn(mesh, S, key_attrs, attrs, agg_attr)
    sums, cnts, ents = fn(
        stk, six, jnp.asarray(los), jnp.asarray(his), jnp.asarray(tss)
    )
    B = los.shape[0]
    n_pages = jnp.sum(stk.local_pages)
    return BatchScanResult(
        sums,
        cnts,
        jnp.zeros((B,), jnp.int32),
        ents,
        jnp.full((B,), n_pages, jnp.int32),
    )


# ---------------------------------------------------------------------------
# The engine facade the executor drives
# ---------------------------------------------------------------------------


class ScanEngine:
    """Dispatch strategy for planned scans over either storage layout.

    ``after_dispatch``, when set, is invoked after every batched group
    dispatch -- the async tuning pipeline hangs its build-quantum
    drain here, so incremental index builds interleave *between* the
    dispatches of one read burst instead of stalling at burst
    boundaries.  The planner's catalog snapshot keeps the burst's
    remaining plans stable while the drained quanta advance
    ``built_pages`` on the live records.
    """

    #: dispatch-strategy vocabulary recorded in ``last_tier``
    TIERS = ("single", "vmap-stacked", "kernel", "shard_map")

    def __init__(self):
        self.after_dispatch = None  # () -> None, set by the runner
        # Mesh execution: None = auto (use a mesh whenever the local
        # devices can place one), False = never, True = required (a
        # placement failure raises instead of silently falling back).
        self.mesh_mode = None
        self.mesh_query_axis = 1  # >1 folds the 2-D query-batch axis
        # Telemetry: the execution tier of the most recent dispatch
        # (the executor stamps it onto ExecStats, the runner
        # aggregates it onto RunResult.execution_tiers).
        self.last_tier = None

    def _scan_mesh(self, n_shards: int, batch: int):
        """The mesh for this dispatch, or None (stacked fallback)."""
        if self.mesh_mode is False:
            return None
        mesh = None
        q = self.mesh_query_axis
        if q > 1 and batch % q == 0:
            mesh = make_scan_mesh(n_shards, q)
        if mesh is None:
            mesh = make_scan_mesh(n_shards)
        if mesh is None and self.mesh_mode is True:
            raise RuntimeError(
                f"mesh execution required but {n_shards} shards cannot "
                f"be placed on {len(jax.local_devices())} local devices"
            )
        return mesh

    def dispatch_complete(self) -> None:
        """Between-dispatch drain point.  The executor calls this after
        each batched group dispatch has been timed, so hook work (build
        quanta) never pollutes the dispatch's measured wall time."""
        if self.after_dispatch is not None:
            self.after_dispatch()

    def scan_batch(
        self,
        table,
        path: str,
        index_state,
        key_attrs: tuple,
        attrs: tuple,
        los,
        his,
        tss,
        agg_attr: int,
        use_kernel: bool = False,
        coverage=None,
    ) -> BatchScanResult:
        """One batched dispatch for a plan group (single dispatch on
        sharded storage too -- the stacked fan-out).  ``coverage`` is
        the plan-pinned ``CoverageView`` for the ``hybrid_masked``
        path (None for every legacy path)."""
        # The Pallas kernels evaluate at most 2 predicate columns;
        # wider conjunctions take the vmapped paths.
        kernel_ok = use_kernel and 1 <= len(attrs) <= 2
        if isinstance(table, ShardedTable):
            return self._scan_batch_sharded(
                table,
                path,
                index_state,
                key_attrs,
                attrs,
                los,
                his,
                tss,
                agg_attr,
                kernel_ok,
                coverage,
            )
        self.last_tier = "single"
        if path == "table":
            if kernel_ok:
                self.last_tier = "kernel"
                return self._kernel_full_scan(
                    table, attrs, los, his, tss, agg_attr
                )
            return batched_full_table_scan(
                table, attrs, los, his, tss, agg_attr
            )
        if path == "hybrid_masked":
            if kernel_ok:
                self.last_tier = "kernel"
                return self._kernel_hybrid_scan_masked(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    coverage,
                )
            return batched_hybrid_scan_masked(
                table,
                index_state,
                key_attrs,
                attrs,
                los,
                his,
                tss,
                agg_attr,
                coverage.mask[0],
                coverage.prefix_len,
            )
        if path in ("hybrid", "hybrid_ps"):  # plain tables have no shards
            if kernel_ok:
                self.last_tier = "kernel"
                return self._kernel_hybrid_scan(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                )
            return batched_hybrid_scan(
                table, index_state, key_attrs, attrs, los, his, tss, agg_attr
            )
        return batched_pure_index_scan(
            table, index_state, key_attrs, attrs, los, his, tss, agg_attr
        )

    # -- kernel paths (TPU; interpret mode on CPU) -----------------------
    @staticmethod
    def _kernel_full_scan(
        table: Table, attrs, los, his, tss, agg_attr: int
    ) -> BatchScanResult:
        from repro.kernels import ops as _kops

        sums, cnts = _kops.scan_table_batched(
            table, attrs, los, his, tss, agg_attr
        )
        B = los.shape[0]
        used = -(-int(table.n_rows) // table.page_size)
        z = jnp.zeros((B,), jnp.int32)
        return BatchScanResult(
            sums, cnts, jnp.full((B,), used, jnp.int32), z, z
        )

    @staticmethod
    def _kernel_hybrid_scan(
        table: Table,
        index: AdHocIndex,
        key_attrs,
        attrs,
        los,
        his,
        tss,
        agg_attr: int,
    ) -> BatchScanResult:
        """Hybrid scans with the table suffix on the multi-query kernel:
        the jnp prefix pass yields per-query stitch points, which flow
        into the kernel as scalar-prefetched ``start_pages`` so blocks
        inside every query's indexed prefix skip their DMA."""
        from repro.kernels import ops as _kops

        pre = batched_hybrid_index_prefix(
            table, index, key_attrs, attrs, los, his, tss, agg_attr
        )
        tbl_sums, tbl_cnts = _kops.scan_table_batched(
            table, attrs, los, his, tss, agg_attr, start_pages=pre.start_page
        )
        psz = table.page_size
        used = ((table.n_rows + psz - 1) // psz).astype(jnp.int32)
        pages = jnp.clip(used - pre.start_page, 0, None).astype(jnp.int32)
        return BatchScanResult(
            pre.agg_sum + tbl_sums,
            pre.count + tbl_cnts,
            pages,
            pre.entries_probed,
            pre.start_page,
        )

    @staticmethod
    def _kernel_hybrid_scan_masked(
        table: Table,
        index: AdHocIndex,
        key_attrs,
        attrs,
        los,
        his,
        tss,
        agg_attr: int,
        cov,
    ) -> BatchScanResult:
        """Masked hybrid scans with the uncovered-page table suffix on
        the kernel: packed coverage words ride the scalar-prefetch
        channel so covered blocks skip their DMA (``pl.when``)."""
        from repro.kernels import ops as _kops

        pre = batched_masked_index_side(
            table,
            index,
            key_attrs,
            attrs,
            los,
            his,
            tss,
            agg_attr,
            cov.mask[0],
            cov.prefix_len,
        )
        tbl_sums, tbl_cnts = _kops.scan_table_batched_masked(
            table, attrs, los, his, tss, agg_attr, cov.words
        )
        used = -(-int(table.n_rows) // table.page_size)
        pages = int((~cov.built_host[:used]).sum())
        B = los.shape[0]
        return BatchScanResult(
            pre.agg_sum + tbl_sums,
            pre.count + tbl_cnts,
            jnp.full((B,), pages, jnp.int32),
            pre.entries_probed,
            pre.start_page,
        )

    @staticmethod
    def _kernel_sharded_hybrid_scan_masked(
        table: ShardedTable,
        index: ShardedIndex,
        key_attrs,
        attrs,
        los,
        his,
        tss,
        agg_attr: int,
        cov,
    ) -> BatchScanResult:
        """Fused masked hybrid scans: the stacked index half plus ONE
        (shard, page-block, query) kernel launch whose per-shard block
        windows come from the packed coverage words."""
        from repro.kernels import ops as _kops

        stk = stacked_shards(table)
        six = stacked_shard_indexes(index)
        psum_, pcnt, ents = _stacked_batched_masked(
            stk,
            six,
            key_attrs,
            attrs,
            los,
            his,
            tss,
            agg_attr,
            cov.mask,
            table_side=False,
        )
        ksums, kcnts = _kops.scan_shards_batched_masked(
            stk, attrs, los, his, tss, agg_attr, cov.words
        )
        pages, starts = _masked_batch_accounting(table, cov, los.shape[0])
        return BatchScanResult(
            psum_ + ksums, pcnt + kcnts, pages, ents, starts
        )

    @staticmethod
    def _kernel_sharded_full_scan(
        table: ShardedTable, attrs, los, his, tss, agg_attr: int
    ) -> BatchScanResult:
        """Fused full scans: every shard rides the (shard, page-block,
        query) grid of one kernel launch, start_pages all zero."""
        from repro.kernels import ops as _kops

        stk = stacked_shards(table)
        B = los.shape[0]
        starts = jnp.zeros((table.n_shards, B), jnp.int32)
        sums, cnts = _kops.scan_shards_batched(
            stk, attrs, los, his, tss, agg_attr, starts
        )
        used = -(-int(table.n_rows) // table.page_size)
        z = jnp.zeros((B,), jnp.int32)
        return BatchScanResult(
            sums, cnts, jnp.full((B,), used, jnp.int32), z, z
        )

    @staticmethod
    def _kernel_sharded_hybrid_scan(
        table: ShardedTable,
        index: ShardedIndex,
        key_attrs,
        attrs,
        los,
        his,
        tss,
        agg_attr: int,
        pershard: bool,
    ) -> BatchScanResult:
        """Fused hybrid scans: the jnp prefix pass emits ONE
        per-(shard, query) ``start_pages`` table -- local stitch points
        under the per-shard stitch, the global stitch point mapped to
        each shard's local page space otherwise -- and the fused kernel
        evaluates every shard's table suffix in one launch."""
        from repro.kernels import ops as _kops

        stk = stacked_shards(table)
        six = stacked_shard_indexes(index)
        if pershard:
            psum, pcnt, ents, local_starts, pages, gstart = (
                _stacked_hybrid_prefix_ps(
                    stk, six, key_attrs, attrs, los, his, tss, agg_attr
                )
            )
        else:
            psum, pcnt, ents, gstart, local_starts = _stacked_hybrid_prefix(
                stk, six, key_attrs, attrs, los, his, tss, agg_attr
            )
            used = _used_pages(table)
            pages = jnp.clip(used - gstart, 0, None).astype(jnp.int32)
        ksums, kcnts = _kops.scan_shards_batched(
            stk, attrs, los, his, tss, agg_attr, local_starts
        )
        return BatchScanResult(
            psum + ksums, pcnt + kcnts, pages, ents, gstart
        )

    # -- sharded single dispatch -----------------------------------------
    def _scan_batch_sharded(
        self,
        table: ShardedTable,
        path: str,
        index_state,
        key_attrs,
        attrs,
        los,
        his,
        tss,
        agg_attr: int,
        kernel_ok: bool,
        coverage=None,
    ) -> BatchScanResult:
        # Mesh placement takes precedence for EVERY family (the old
        # pmap fan-out only covered uniform full-table scans and fell
        # back silently; the tier below is the telemetry for that
        # decision).  On a mesh the kernel flag selects the Pallas
        # suffix per locally-owned shard only off-CPU -- interpret
        # mode keeps the bit-identical jnp mapped bodies.
        mesh = self._scan_mesh(table.n_shards, los.shape[0])
        if mesh is not None:
            from repro.kernels import ops as _kops

            self.last_tier = "shard_map"
            mesh_kernel = kernel_ok and not _kops.interpret_mode()
            if path == "table":
                return mesh_batched_full_table_scan(
                    table, attrs, los, his, tss, agg_attr, mesh, mesh_kernel
                )
            if path == "hybrid":
                return mesh_batched_hybrid_scan(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    mesh,
                    mesh_kernel,
                )
            if path == "hybrid_ps":
                return mesh_batched_hybrid_scan_pershard(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    mesh,
                    mesh_kernel,
                )
            if path == "hybrid_masked":
                return mesh_batched_hybrid_scan_masked(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    coverage,
                    mesh,
                    mesh_kernel,
                )
            return mesh_batched_pure_index_scan(
                table,
                index_state,
                key_attrs,
                attrs,
                los,
                his,
                tss,
                agg_attr,
                mesh,
            )
        self.last_tier = "kernel" if kernel_ok else "vmap-stacked"
        if path == "table":
            if kernel_ok:
                return self._kernel_sharded_full_scan(
                    table, attrs, los, his, tss, agg_attr
                )
            return sharded_batched_full_table_scan(
                table, attrs, los, his, tss, agg_attr
            )
        if path == "hybrid":
            if kernel_ok:
                return self._kernel_sharded_hybrid_scan(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    pershard=False,
                )
            return sharded_batched_hybrid_scan(
                table, index_state, key_attrs, attrs, los, his, tss, agg_attr
            )
        if path == "hybrid_ps":
            if kernel_ok:
                return self._kernel_sharded_hybrid_scan(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    pershard=True,
                )
            return sharded_batched_hybrid_scan_pershard(
                table, index_state, key_attrs, attrs, los, his, tss, agg_attr
            )
        if path == "hybrid_masked":
            if kernel_ok:
                return self._kernel_sharded_hybrid_scan_masked(
                    table,
                    index_state,
                    key_attrs,
                    attrs,
                    los,
                    his,
                    tss,
                    agg_attr,
                    coverage,
                )
            return sharded_batched_hybrid_scan_masked(
                table,
                index_state,
                key_attrs,
                attrs,
                los,
                his,
                tss,
                agg_attr,
                coverage,
            )
        return sharded_batched_pure_index_scan(
            table, index_state, key_attrs, attrs, los, his, tss, agg_attr
        )
