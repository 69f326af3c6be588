"""Ad-hoc secondary indexes with partial, incremental construction.

Implements the three index-population schemes compared in Section II-B
of the paper:

* ``FULL`` -- the index is only usable once every page is indexed
  (online indexing a la DB2/SQL-Server advisors).
* ``VBP``  -- value-based partial: entries are added for the value
  sub-domain touched by each query (database cracking / SMIX /
  holistic indexing).  Requires per-index sub-domain metadata (the
  "covering tree"); population is driven by query predicates and can
  cause latency spikes proportional to the sub-domain population.
* ``VAP``  -- value-agnostic partial (the paper's proposal): entries
  are added for a fixed number of *pages* per tuning cycle, in
  ascending page order, independent of any attribute value
  distribution.  The only metadata needed is ``built_pages``.

The index is a lexicographically sorted (key, rid) array with fixed
capacity.  Multi-attribute indexes (up to two attributes -- the
paper's TUNER benchmark uses one- and two-attribute predicates) keep a
composite int32 key pair ``(key_hi, key_lo)``; JAX's default int32
regime forbids a packed int64 key, so comparisons are explicit
lexicographic pair compares.  Invalid slots hold (INT32_MAX,
INT32_MAX) which sorts after any real key (attribute values are
assumed < INT32_MAX; the TUNER domain is [1, 1m]).

In-order build invariant (relied on by the hybrid scan, Section III):
VAP entries for page p are only inserted after pages < p are fully
indexed, except for pages being built in the current cycle, hence
rho_m <= rho_i + pages_per_cycle and every non-prefix page is table
scanned.

Coverage-bitmap contract (crack-on-scan generalization)
-------------------------------------------------------
``PageCoverage`` generalizes the built prefix to an arbitrary
built-page *bitmap* over global page ids, which retires the global
page-order constraint: hot-range-first builds, crack-on-scan adoption
and cold-page decay all become bit flips plus ``build_pages_at``
merges.  The exactness rules every consumer relies on:

* Hard invariant: a set bit means the page is FULLY indexed (every
  occupied slot of a fully-populated page has an entry).  The
  partially-filled append-watermark page is never marked covered --
  same rule as ``build_pages_vap``'s ``full_pages`` clamp.
* Entries MAY exist for uncovered pages (decay clears bits without
  compacting entries; an in-progress build has merged but not yet
  flipped).  Masked scans drop them on the index side
  (``idx_keep = idx_match & covered[pg]``) and re-discover the rows on
  the table side, which scans exactly the uncovered pages -- so any
  consistent (index, coverage) pair yields exactly-once results.
* Prefix degeneracy: a bitmap that IS a prefix of length
  ``built_pages`` (and has no stray entries beyond it,
  ``legacy_prefix_ok``) must route through the legacy ``start_page``
  paths and is bit-identical to them in results AND accounting; the
  masked path reproduces the same bits for that shape (property-tested
  in tests/test_coverage_bitmap.py), so routing is a pure fast-path
  choice, never a semantics choice.
* Coverage is host-managed (numpy) and versioned; device views
  (bool masks, packed int32 words for the Pallas kernels) are cached
  per version so a bitmap upload happens once per mutation, not once
  per query.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.table import (INF_TS, ShardedTable, Table, global_rids,
                              identity_lru_lookup)
from repro.obs import host_read

I32_MAX = jnp.int32(2**31 - 1)
I32_MIN = jnp.int32(-(2**31))

KeyPair = Tuple[jax.Array, jax.Array]  # (hi, lo) component arrays/scalars


class AdHocIndex(NamedTuple):
    """Sorted partial index over one or two attributes of a Table."""

    key_hi: jax.Array       # (capacity,) int32 leading key component
    key_lo: jax.Array       # (capacity,) int32 secondary component (0 if 1-attr)
    rids: jax.Array         # (capacity,) int32
    n_entries: jax.Array    # () int32
    built_pages: jax.Array  # () int32  == rho_i + 1 (fully indexed prefix)

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]


def make_index(capacity: int) -> AdHocIndex:
    return AdHocIndex(
        key_hi=jnp.full((capacity,), I32_MAX, jnp.int32),
        key_lo=jnp.full((capacity,), I32_MAX, jnp.int32),
        rids=jnp.zeros((capacity,), jnp.int32),
        n_entries=jnp.zeros((), jnp.int32),
        built_pages=jnp.zeros((), jnp.int32),
    )


def make_keys(cols: Sequence[jax.Array]) -> KeyPair:
    """Composite key components from 1 or 2 int32 columns."""
    if len(cols) == 1:
        return cols[0].astype(jnp.int32), jnp.zeros_like(cols[0], jnp.int32)
    if len(cols) == 2:
        return cols[0].astype(jnp.int32), cols[1].astype(jnp.int32)
    raise ValueError("indexes support 1 or 2 key attributes")


def key_range(lo0, hi0, lo1=None, hi1=None) -> Tuple[KeyPair, KeyPair]:
    """Inclusive lexicographic key range for a range predicate.

    For 2-attribute indexes the range covers the leading attribute's
    interval; rows matching the leading bound but outside the second
    attribute's interval are post-filtered by the scan's predicate
    re-check.
    """
    lo0 = jnp.asarray(lo0, jnp.int32)
    hi0 = jnp.asarray(hi0, jnp.int32)
    if lo1 is None:
        return (lo0, jnp.asarray(0, jnp.int32)), (hi0, jnp.asarray(0, jnp.int32))
    return ((lo0, jnp.asarray(lo1, jnp.int32)),
            (hi0, jnp.asarray(hi1, jnp.int32)))


def keys_geq(kh, kl, b: KeyPair) -> jax.Array:
    return (kh > b[0]) | ((kh == b[0]) & (kl >= b[1]))


def keys_leq(kh, kl, b: KeyPair) -> jax.Array:
    return (kh < b[0]) | ((kh == b[0]) & (kl <= b[1]))


def keys_in_range(kh, kl, lo: KeyPair, hi: KeyPair) -> jax.Array:
    return keys_geq(kh, kl, lo) & keys_leq(kh, kl, hi)


def _ranks_chunk(m: int, capacity: int) -> bool:
    """Whether ``_merge_entries`` ranks a chunk of ``m`` new entries
    into an index of ``capacity`` slots, rather than sorting both.

    Ranking makes m x log2(capacity) element gathers; sorting the
    concatenation costs about one such gather per entry (on a TPU v5e
    at a 15M capacity: 12.7 ms to rank 16,384 entries, 0.77 s for a
    build that sorts 30M).  So a chunk ranks while
    m x log2(capacity) < capacity: a VAP build quantum of a large table
    does; ``build_full`` and a value-based population of the whole
    capacity do not.
    """
    return m * capacity.bit_length() < capacity


def _merge_entries(old, new):
    """Merge ``new`` (key_hi, key_lo, rid) triples into an index's
    sorted ``old`` arrays and keep the index's length (padding keys
    sort last).

    ``old`` is sorted by key, as every build step leaves it; ``new`` is
    in any order.  The result equals a stable lexsort of ``old`` then
    ``new`` by (key_hi, key_lo) on every slot, padding included: on
    equal keys old entries come first, then new ones in their given
    order.

    A chunk that ``_ranks_chunk`` admits is sorted alone.  Each new
    entry then finds by binary search how many old keys are <= its own
    (``r``): new entry j (in sorted order) lands at ``r[j] + j``, and
    old entry i moves right by ``#{j : r[j] <= i}``, which a cumulative
    sum gives and ``_shift_right`` applies in linear passes, so nothing
    of the capacity's length is sorted or gathered.  A larger chunk is
    sorted together with the index, which then costs less.
    """
    (oh, ol, orr), (nh, nl, nr) = old, new
    capacity, m = oh.shape[0], nh.shape[0]
    if not _ranks_chunk(m, capacity):
        mh, ml, mr = (jnp.concatenate(pair) for pair in zip(old, new))
        order = jnp.lexsort((ml, mh))[:capacity]
        return mh[order], ml[order], mr[order]

    order = jnp.lexsort((nl, nh))
    nh, nl, nr = nh[order], nl[order], nr[order]
    # r[j] = #{old keys <= new key j}: the largest r with old[r-1] <= key.
    r = jnp.zeros((m,), jnp.int32)
    step = 1 << (capacity.bit_length() - 1)
    while step:
        cand = r + step
        at = jnp.clip(cand - 1, 0, capacity - 1)
        take = (cand <= capacity) & keys_leq(oh[at], ol[at], (nh, nl))
        r = jnp.where(take, cand, r)
        step >>= 1

    shift = jnp.cumsum(jnp.zeros((capacity,), jnp.int32)
                       .at[r].add(1, mode="drop"), dtype=jnp.int32)
    oh, ol, orr = _shift_right((oh, ol, orr), shift, m)
    dest = r + jnp.arange(m, dtype=jnp.int32)
    return (oh.at[dest].set(nh, mode="drop"),
            ol.at[dest].set(nl, mode="drop"),
            orr.at[dest].set(nr, mode="drop"))


def _shift_right(arrays, shift, max_shift: int):
    """Move element i of each array to ``i + shift[i]``, dropping what
    lands past the end; slots nothing lands on keep stale values.

    ``shift`` must be non-decreasing and at most ``max_shift``.  Bits
    go from the highest down: after the passes for bits >= b, element
    i sits at i + (shift[i] with the bits below b cleared), which is
    strictly increasing in i, so no two elements ever meet.  Each pass
    is one select between the arrays and their copy shifted by 2**b;
    a slot whose element left keeps a zero shift, so it never moves.
    """
    n = shift.shape[0]
    for b in reversed(range(max(max_shift, 1).bit_length())):
        d = 1 << b
        moves = (shift & d) != 0
        if d >= n:
            shift = jnp.where(moves, 0, shift)
            continue

        def right(x):
            return jnp.pad(x[:n - d], (d, 0))

        came = right(moves)
        arrays = tuple(jnp.where(came, right(x), x) for x in arrays)
        shift = jnp.where(came, right(shift), jnp.where(moves, 0, shift))
    return arrays


# ---------------------------------------------------------------------------
# VAP: value-agnostic page-wise population (the paper's scheme)
# ---------------------------------------------------------------------------

# Smallest page bucket of a VAP build step (the tuner's default
# per-cycle cap is 64 pages).
_MIN_BUILD_BUCKET = 64


def build_pages_vap(index: AdHocIndex, table: Table, key_attrs: tuple,
                    pages_per_cycle: int) -> AdHocIndex:
    """One VAP tuning-cycle step: index the next ``pages_per_cycle`` pages.

    Cost is O(pages_per_cycle * page_size) extraction and sort plus,
    where the chunk is small beside the index, a linear merge into the
    key arrays (``_merge_entries``) -- independent of attribute value
    distribution, which is precisely the property Section III-A argues
    for.

    The page count is a traced operand inside a static power-of-two
    bucket (at least 64 pages, at most the table), so build quanta of
    every size up to the bucket share one compiled program.  Pages
    past the count are masked exactly like pages past the full-page
    watermark.
    """
    n = int(pages_per_cycle)
    bucket = max(_MIN_BUILD_BUCKET, 1 << max(n - 1, 0).bit_length())
    bucket = min(bucket, table.n_pages)
    return _build_pages_vap(index, table, key_attrs,
                            jnp.asarray(n, jnp.int32), bucket=bucket)


@functools.partial(jax.jit, static_argnames=("key_attrs", "bucket"))
def _build_pages_vap(index: AdHocIndex, table: Table, key_attrs: tuple,
                     n_pages, bucket: int) -> AdHocIndex:
    psz = table.page_size
    start = index.built_pages
    # The quantum's pages are consecutive: slice ``bucket`` of them out
    # of each key plane, from a start clamped into the table, and mask
    # the pages before ``start``.  A slice of one attribute copies no
    # other column, where a gather of whole pages would.
    first = jnp.clip(start, 0, table.n_pages - bucket)
    pages = first + jnp.arange(bucket, dtype=jnp.int32)
    # Only *fully populated* pages may be indexed and counted as built:
    # a partially filled watermark page must stay inside the table-scan
    # region, otherwise later appends to it would be invisible.
    full_pages = (table.n_rows // psz).astype(jnp.int32)
    in_range = ((pages >= start) & (pages - start < n_pages)
                & (pages < full_pages))
    cols = [jax.lax.dynamic_slice(table.data, (first, 0, a),
                                  (bucket, psz, 1))[:, :, 0]
            for a in key_attrs]
    begin = jax.lax.dynamic_slice(table.begin_ts, (first, 0), (bucket, psz))
    index = _merge_pages(index, pages, in_range, cols, begin)
    built = jnp.minimum(start + n_pages, full_pages)
    built = jnp.maximum(built, start)  # never regress
    return index._replace(built_pages=built)


def _merge_pages(index: AdHocIndex, pages, page_ok, cols,
                 begin) -> AdHocIndex:
    """Merge the entries of whole pages into ``index``.

    ``cols`` are the key attributes' (P, page_size) planes of the pages
    ``pages`` and ``begin`` their begin timestamps; pages where
    ``page_ok`` is false add nothing.  Only slots that ever held a row
    are indexed; dead versions stay indexed (the scan re-checks MVCC
    visibility).
    """
    psz = begin.shape[1]
    kh, kl = make_keys(cols)
    kh, kl = kh.reshape(-1), kl.reshape(-1)
    slot = jnp.arange(psz, dtype=jnp.int32)[None, :]
    new_rids = (pages[:, None] * psz + slot).reshape(-1)
    valid = ((begin < INF_TS) & page_ok[:, None]).reshape(-1)
    kh = jnp.where(valid, kh, I32_MAX)
    kl = jnp.where(valid, kl, I32_MAX)

    mh, ml, mr = _merge_entries(
        (index.key_hi, index.key_lo, index.rids),
        (kh, kl, new_rids.astype(jnp.int32)))
    n_entries = index.n_entries + jnp.sum(valid, dtype=jnp.int32)
    return AdHocIndex(mh, ml, mr, n_entries, index.built_pages)


def build_full(index: AdHocIndex, table: Table, key_attrs: tuple) -> AdHocIndex:
    """FULL scheme: index every page in one (expensive) shot."""
    return build_pages_vap(index, table, key_attrs,
                           pages_per_cycle=table.n_pages)


# ---------------------------------------------------------------------------
# Sharded VAP/FULL: one local index per table shard
# ---------------------------------------------------------------------------

class ShardedIndex(NamedTuple):
    """Per-shard AdHocIndex state over a ShardedTable.

    Each shard's index holds *local* rids into its own shard and a
    shard-local ``built_pages`` prefix.  Because the sharded build
    walks global page order (round-robin over shards -- see
    ``sharded_build_pages_vap``), the union of the local prefixes is
    always the global prefix [0, sum(built_pages)), which preserves
    the hybrid scan's stitch invariant across any shard count.
    """

    shards: Tuple[AdHocIndex, ...]

    @property
    def built_pages(self) -> jax.Array:
        """Global fully-indexed page prefix length (== rho_i + 1)."""
        out = self.shards[0].built_pages
        for ix in self.shards[1:]:
            out = out + ix.built_pages
        return out

    @property
    def n_entries(self) -> jax.Array:
        out = self.shards[0].n_entries
        for ix in self.shards[1:]:
            out = out + ix.n_entries
        return out

    @property
    def capacity(self) -> int:
        return sum(ix.capacity for ix in self.shards)


def make_sharded_index(table: ShardedTable) -> ShardedIndex:
    return ShardedIndex(tuple(make_index(t.capacity) for t in table.shards))


# ---------------------------------------------------------------------------
# Stacked shard indexes: the fused single-dispatch layout's index side
# ---------------------------------------------------------------------------
#
# Companion of ``table.stacked_shards``: every shard's sorted entry
# arrays stacked on one leading axis, padded to the max shard capacity
# with (I32_MAX, I32_MAX) keys and rid 0.  Padded slots sit at
# positions >= the shard's real capacity, and ``n_entries`` never
# exceeds the real capacity, so the ``ar < n_entries`` guard in
# ``index_range_scan`` masks them off -- probe results and
# ``entries_probed`` accounting are bit-identical to the per-shard
# arrays.  Cached by shards-tuple identity exactly like the table
# stack: build quanta and VBP populations replace the tuple, so a
# stale stack can never be returned.


_INDEX_STACK_CACHE: OrderedDict = OrderedDict()
# Pins one padded copy per entry; sized for the sharded indexes a
# burst can actually touch (a handful of live BuiltIndex records),
# not for dead generations left behind by build quanta.
_INDEX_STACK_CACHE_MAX = 8


def _stack_shard_indexes(index: "ShardedIndex") -> AdHocIndex:
    cmax = max(ix.capacity for ix in index.shards)

    def padv(x, fill):
        pad = cmax - x.shape[0]
        if pad == 0:
            return x
        return jnp.pad(x, ((0, pad),), constant_values=fill)

    return AdHocIndex(
        key_hi=jnp.stack([padv(ix.key_hi, I32_MAX) for ix in index.shards]),
        key_lo=jnp.stack([padv(ix.key_lo, I32_MAX) for ix in index.shards]),
        rids=jnp.stack([padv(ix.rids, 0) for ix in index.shards]),
        n_entries=jnp.stack([ix.n_entries for ix in index.shards]),
        built_pages=jnp.stack([ix.built_pages for ix in index.shards]),
    )


def stacked_shard_indexes(index: "ShardedIndex") -> AdHocIndex:
    """Cached stacked/padded per-shard index arrays (leading shard
    axis on every ``AdHocIndex`` leaf)."""
    return identity_lru_lookup(
        _INDEX_STACK_CACHE, _INDEX_STACK_CACHE_MAX, index.shards,
        lambda: _stack_shard_indexes(index))


def _count_owned_below(bound: int, shard: int, n_shards: int) -> int:
    """#{global page p < bound : p % n_shards == shard} (host-side)."""
    return max(0, -(-(bound - shard) // n_shards))


def sharded_build_pages_vap(index: ShardedIndex, table: ShardedTable,
                            key_attrs: tuple,
                            pages_per_cycle: int) -> ShardedIndex:
    """One VAP cycle over sharded storage: index the next
    ``pages_per_cycle`` pages *in global page order*, which round-robins
    the build budget across shards (global page p extends shard p % S).
    The set of built pages -- and therefore every downstream scan,
    stitch point and accounting value -- is bit-identical to the
    single-shard ``build_pages_vap`` at the same cumulative budget.
    """
    S = len(index.shards)
    built = sum(int(ix.built_pages) for ix in index.shards)
    new_shards = []
    for s, (ix, t) in enumerate(zip(index.shards, table.shards)):
        step = (_count_owned_below(built + pages_per_cycle, s, S)
                - _count_owned_below(built, s, S))
        if step > 0:
            ix = build_pages_vap(ix, t, key_attrs, pages_per_cycle=step)
        new_shards.append(ix)
    return ShardedIndex(tuple(new_shards))


# ---------------------------------------------------------------------------
# VBP: value-based partial population (cracking / SMIX / holistic style)
# ---------------------------------------------------------------------------

class VbpState(NamedTuple):
    """VBP index + covering metadata.

    ``cov_*`` is a fixed-capacity interval set over the composite key
    domain -- the "covering tree" of SMIX.  An interval means every
    tuple whose key falls inside it is present in the index.
    ``in_index`` marks rids already indexed so overlapping sub-domain
    populations never create duplicate entries.
    """
    index: AdHocIndex
    cov_lo_hi: jax.Array  # (max_intervals,) int32 -- lower bound, hi comp
    cov_lo_lo: jax.Array  # (max_intervals,) int32 -- lower bound, lo comp
    cov_hi_hi: jax.Array  # (max_intervals,) int32 -- upper bound, hi comp
    cov_hi_lo: jax.Array  # (max_intervals,) int32 -- upper bound, lo comp
    n_cov: jax.Array      # () int32
    in_index: jax.Array   # (row_capacity,) bool


def make_vbp(capacity: int, max_intervals: int = 64) -> VbpState:
    return VbpState(
        index=make_index(capacity),
        cov_lo_hi=jnp.full((max_intervals,), I32_MAX, jnp.int32),
        cov_lo_lo=jnp.full((max_intervals,), I32_MAX, jnp.int32),
        cov_hi_hi=jnp.full((max_intervals,), I32_MIN, jnp.int32),
        cov_hi_lo=jnp.full((max_intervals,), I32_MIN, jnp.int32),
        n_cov=jnp.zeros((), jnp.int32),
        in_index=jnp.zeros((capacity,), bool),
    )


def vbp_is_covered(state: VbpState, lo: KeyPair, hi: KeyPair) -> jax.Array:
    """True iff [lo, hi] lies inside one covered interval."""
    cov_leq_lo = keys_leq(state.cov_lo_hi, state.cov_lo_lo, lo)   # cov_lo <= lo
    hi_leq_cov = keys_geq(state.cov_hi_hi, state.cov_hi_lo, hi)   # hi <= cov_hi
    inside = cov_leq_lo & hi_leq_cov
    inside &= jnp.arange(state.cov_lo_hi.shape[0]) < state.n_cov
    return jnp.any(inside)


@functools.partial(jax.jit,
                   static_argnames=("key_attrs", "max_add"))
def vbp_populate_subdomain(state: VbpState, table: Table, key_attrs: tuple,
                           lo: KeyPair, hi: KeyPair, ts,
                           max_add: int) -> Tuple[VbpState, jax.Array]:
    """Add index entries for every tuple whose key is in [lo, hi].

    This is the value-based population step: its cost is proportional
    to the number of tuples in the sub-domain (hence the latency
    spikes of Figures 2 and 7).  Returns (state, n_added); n_added is
    the work performed, which the benchmark runner charges to the
    query that triggered the population.
    """
    cols = [table.data[:, :, a] for a in key_attrs]
    kh, kl = make_keys(cols)
    kh, kl = kh.reshape(-1), kl.reshape(-1)
    occupied = (table.begin_ts < INF_TS).reshape(-1)
    already = vbp_is_covered(state, lo, hi)
    want = (occupied & keys_in_range(kh, kl, lo, hi)
            & ~already & ~state.in_index)
    n_want = jnp.sum(want, dtype=jnp.int32)

    order = jnp.argsort(~want, stable=True)
    take = order[:max_add].astype(jnp.int32)
    ok = jnp.arange(max_add) < jnp.minimum(n_want, max_add)
    nk_hi = jnp.where(ok, kh[take], I32_MAX)
    nk_lo = jnp.where(ok, kl[take], I32_MAX)

    idx = state.index
    mh, ml, mr = _merge_entries((idx.key_hi, idx.key_lo, idx.rids),
                                (nk_hi, nk_lo, take))
    new_index = AdHocIndex(mh, ml, mr,
                           idx.n_entries + jnp.minimum(n_want, max_add),
                           idx.built_pages)
    in_index = state.in_index.at[take].set(state.in_index[take] | ok)
    # Record coverage only if the whole sub-domain fit this cycle.
    fits = (n_want <= max_add) & ~already
    cov = _record_coverage(state, fits, lo, hi)
    return (VbpState(new_index, *cov, in_index),
            jnp.minimum(n_want, max_add))


def _record_coverage(state, fits, lo: KeyPair, hi: KeyPair):
    """Append [lo, hi] to the covering interval set when ``fits``;
    shared by the single-table and sharded population steps (``state``
    only needs the ``cov_*``/``n_cov`` fields)."""
    slot = jnp.minimum(state.n_cov, state.cov_lo_hi.shape[0] - 1)

    def upd(arr, val):
        return arr.at[slot].set(jnp.where(fits, val, arr[slot]))

    return (upd(state.cov_lo_hi, lo[0]), upd(state.cov_lo_lo, lo[1]),
            upd(state.cov_hi_hi, hi[0]), upd(state.cov_hi_lo, hi[1]),
            state.n_cov + jnp.where(fits, 1, 0).astype(jnp.int32))


def vbp_invalidate_coverage(state):
    """Drop coverage claims after table mutations (inserts create rows
    the covering intervals do not know about).  Index entries stay --
    the scan re-checks visibility -- but pure index scans are no
    longer legal until sub-domains are re-populated.  Works on both
    ``VbpState`` and ``ShardedVbpState``."""
    return state._replace(n_cov=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Sharded VBP: per-shard sorted entries, global covering metadata
# ---------------------------------------------------------------------------

class ShardedVbpState(NamedTuple):
    """VBP over sharded storage.

    Sorted entries are per shard (local rids) so shard-local scans need
    no cross-shard gathers, but the covering-interval metadata and the
    ``in_index`` dedup bitmap live on the *global* key/rid space: an
    interval claims every tuple in the sub-domain regardless of which
    shard holds it, and the "first max_add wanted rows in rid order"
    population budget is a global selection (same shape as the sharded
    UPDATE's selection -- see table.sharded_update_rows).
    """

    shards: Tuple[AdHocIndex, ...]
    cov_lo_hi: jax.Array   # (max_intervals,) int32
    cov_lo_lo: jax.Array
    cov_hi_hi: jax.Array
    cov_hi_lo: jax.Array
    n_cov: jax.Array       # () int32
    in_index: jax.Array    # (global row capacity,) bool

    @property
    def n_entries(self) -> jax.Array:
        out = self.shards[0].n_entries
        for ix in self.shards[1:]:
            out = out + ix.n_entries
        return out


def make_sharded_vbp(table: ShardedTable,
                     max_intervals: int = 64) -> ShardedVbpState:
    proto = make_vbp(1, max_intervals)   # reuse the cov-array layout
    return ShardedVbpState(
        shards=tuple(make_index(t.capacity) for t in table.shards),
        cov_lo_hi=proto.cov_lo_hi, cov_lo_lo=proto.cov_lo_lo,
        cov_hi_hi=proto.cov_hi_hi, cov_hi_lo=proto.cov_hi_lo,
        n_cov=proto.n_cov,
        in_index=jnp.zeros((table.capacity,), bool))


@functools.partial(jax.jit,
                   static_argnames=("key_attrs", "max_add"))
def sharded_vbp_populate_subdomain(state: ShardedVbpState,
                                   table: ShardedTable, key_attrs: tuple,
                                   lo: KeyPair, hi: KeyPair, ts,
                                   max_add: int
                                   ) -> Tuple[ShardedVbpState, jax.Array]:
    """Sharded value-based population, bit-identical to the single-table
    ``vbp_populate_subdomain``: per-shard key planes are scattered into
    global rid order, the wanted set and the max_add budget selection
    run globally, and the chosen rows merge into their owning shard's
    sorted entries."""
    S = len(table.shards)
    psz = table.page_size
    capacity = table.capacity
    gkh = jnp.zeros((capacity,), jnp.int32)
    gkl = jnp.zeros((capacity,), jnp.int32)
    gocc = jnp.zeros((capacity,), bool)
    for s, t in enumerate(table.shards):
        kh, kl = make_keys([t.data[:, :, a] for a in key_attrs])
        rid_map = global_rids(t.n_pages, s, S, psz)
        gkh = gkh.at[rid_map].set(kh.reshape(-1))
        gkl = gkl.at[rid_map].set(kl.reshape(-1))
        gocc = gocc.at[rid_map].set((t.begin_ts < INF_TS).reshape(-1))

    already = vbp_is_covered(state, lo, hi)
    want = (gocc & keys_in_range(gkh, gkl, lo, hi)
            & ~already & ~state.in_index)
    n_want = jnp.sum(want, dtype=jnp.int32)

    order = jnp.argsort(~want, stable=True)
    take = order[:max_add].astype(jnp.int32)
    ok = jnp.arange(max_add) < jnp.minimum(n_want, max_add)
    nk_hi = jnp.where(ok, gkh[take], I32_MAX)
    nk_lo = jnp.where(ok, gkl[take], I32_MAX)
    gp, sl = take // psz, take % psz
    owner, lp = gp % S, gp // S

    new_shards = []
    for s, ix in enumerate(state.shards):
        ok_s = ok & (owner == s)
        mh, ml, mr = _merge_entries(
            (ix.key_hi, ix.key_lo, ix.rids),
            (jnp.where(ok_s, nk_hi, I32_MAX), jnp.where(ok_s, nk_lo, I32_MAX),
             jnp.where(ok_s, lp * psz + sl, 0).astype(jnp.int32)))
        new_shards.append(AdHocIndex(
            mh, ml, mr, ix.n_entries + jnp.sum(ok_s, dtype=jnp.int32),
            ix.built_pages))
    in_index = state.in_index.at[take].set(state.in_index[take] | ok)
    fits = (n_want <= max_add) & ~already
    cov = _record_coverage(state, fits, lo, hi)
    return (ShardedVbpState(tuple(new_shards), *cov, in_index),
            jnp.minimum(n_want, max_add))


# ---------------------------------------------------------------------------
# Resumable build quanta (the async tuning pipeline's apply step)
# ---------------------------------------------------------------------------

def advance_build(state, table, key_attrs: tuple, pages: int):
    """One resumable build quantum: advance the built prefix by up to
    ``pages`` pages from the current watermark.

    Dispatches to ``build_pages_vap`` / ``sharded_build_pages_vap`` by
    storage layout and returns ``(state, pages_done)``.  Because the
    VAP build is a pure function of ``built_pages``, a cycle's budget
    can be applied as one call or as any sequence of smaller quanta
    (``split_build_pages``) interleaved with query dispatches: the
    resulting entry set, ``built_pages`` watermark and total work are
    identical, only the schedule differs.  ``pages_done < pages`` when
    the build clamps at the table's full-page watermark (the unused
    budget is the caller's to carry over).
    """
    before = int(host_read(state.built_pages, "index.advance_build.before"))
    if isinstance(state, ShardedIndex):
        state = sharded_build_pages_vap(state, table, key_attrs,
                                        pages_per_cycle=int(pages))
    else:
        state = build_pages_vap(state, table, key_attrs,
                                pages_per_cycle=int(pages))
    after = host_read(state.built_pages, "index.advance_build.after")
    return state, int(after) - before


def build_pages_remaining(state, table) -> int:
    """Fully-populated pages not yet covered by the built prefix."""
    n_rows = host_read(table.n_rows, "index.pages_remaining.n_rows")
    built = host_read(state.built_pages, "index.pages_remaining.built")
    return max(int(n_rows) // table.page_size - int(built), 0)


# ---------------------------------------------------------------------------
# Per-shard build quanta (shard-aware tuning: relaxed prefix invariant)
# ---------------------------------------------------------------------------
#
# ``sharded_build_pages_vap`` keeps the union of the shard-local
# prefixes a *global* page prefix -- the invariant the global hybrid
# stitch relies on.  Shard-aware tuning relaxes it: each shard's local
# prefix advances independently (budget routed by forecast per-shard
# utility), and the hybrid scan stitches per shard instead
# (engine._pershard_stitch).  Every shard still builds its
# own local pages strictly in order, so the per-shard in-order
# invariant -- the one correctness actually needs -- is untouched.


def shard_full_pages(table: ShardedTable) -> list:
    """Fully-populated (indexable) page count per shard."""
    return [int(t.n_rows) // t.page_size for t in table.shards]


def shard_remaining_pages(state: ShardedIndex, table: ShardedTable) -> list:
    """Unbuilt fully-populated pages per shard."""
    return [max(f - int(ix.built_pages), 0)
            for f, ix in zip(shard_full_pages(table), state.shards)]


def prefix_is_round_robin(state: ShardedIndex) -> bool:
    """True iff the shard-local prefixes still partition one global
    page prefix under the round-robin page map -- i.e. the legacy
    global stitch is sound for this index state."""
    S = len(state.shards)
    built = [int(ix.built_pages) for ix in state.shards]
    total = sum(built)
    return all(b == _count_owned_below(total, s, S)
               for s, b in enumerate(built))


def advance_build_shard(state: ShardedIndex, table: ShardedTable,
                        key_attrs: tuple, shard: int, pages: int):
    """One shard-targeted build quantum: advance ``shard``'s local
    built prefix by up to ``pages`` pages.  Returns (state, pages_done)
    exactly like ``advance_build``; the quantum clamps at the shard's
    own full-page watermark."""
    ix, t = state.shards[shard], table.shards[shard]
    before = int(host_read(ix.built_pages, "index.advance_build.before"))
    ix = build_pages_vap(ix, t, key_attrs, pages_per_cycle=int(pages))
    shards = list(state.shards)
    shards[shard] = ix
    after = host_read(ix.built_pages, "index.advance_build.after")
    return ShardedIndex(tuple(shards)), int(after) - before


def split_build_pages(pages: int, quantum_pages: int | None):
    """Slice one cycle's page budget into resumable build quanta.

    ``quantum_pages=None`` (or a quantum at least as large as the
    budget) keeps the whole slice as a single quantum -- the
    deterministic-interleave mode relies on this to reproduce the
    serialized build-call sequence exactly.
    """
    if pages <= 0:
        return []
    if quantum_pages is None or quantum_pages <= 0 or quantum_pages >= pages:
        return [pages]
    out = []
    left = pages
    while left > 0:
        step = min(quantum_pages, left)
        out.append(step)
        left -= step
    return out


# ---------------------------------------------------------------------------
# Page-coverage bitmap (crack-on-scan / hot-range builds / decay)
# ---------------------------------------------------------------------------

COVERAGE_WORD_BITS = 32


class PageCoverage:
    """Host-managed built-page bitmap over GLOBAL page ids.

    See the module docstring for the exactness contract.  The bitmap
    lives outside the jitted index pytrees on purpose: mutations
    (crack adoption, hot-range quanta, decay) happen host-side between
    dispatches, and keeping ``AdHocIndex`` unchanged preserves every
    existing stacking cache, vmap axis spec and kernel operand layout.
    Device views are derived on demand and cached by ``version``.
    """

    __slots__ = ("built", "version", "max_entry_page", "page_size", "_cache")

    def __init__(self, n_pages: int, page_size: int = 0):
        self.built = np.zeros(int(n_pages), bool)
        self.version = 0
        self.page_size = int(page_size)  # size accounting (decay cap)
        # Highest page id entries were ever emitted for: the legacy
        # prefix routes are only sound when no entries exist beyond
        # the prefix (a stale entry would pull rho_m past unindexed
        # pages).  -1 == no entries yet.
        self.max_entry_page = -1
        self._cache: dict = {}

    # ---- constructors / shape queries --------------------------------
    @classmethod
    def from_prefix(cls, n_pages: int, prefix: int,
                    page_size: int = 0) -> "PageCoverage":
        cov = cls(n_pages, page_size)
        prefix = int(prefix)
        if prefix > 0:
            cov.built[:prefix] = True
            cov.max_entry_page = prefix - 1
        return cov

    @property
    def n_pages(self) -> int:
        return self.built.shape[0]

    def count(self) -> int:
        return int(self.built.sum())

    def prefix_len(self) -> int:
        """Length of the leading all-built run."""
        unbuilt = np.flatnonzero(~self.built)
        return int(unbuilt[0]) if unbuilt.size else self.n_pages

    def is_prefix(self) -> bool:
        """True iff the built pages are exactly [0, prefix_len)."""
        return self.count() == self.prefix_len()

    def legacy_prefix_ok(self, built_pages: int) -> bool:
        """May scans route through the legacy ``start_page`` paths?
        Requires the bitmap to be the exact prefix the index's
        ``built_pages`` watermark claims AND no stray entries beyond
        it (crack adoption ahead of the prefix, or decay that cleared
        bits without compacting, both force the masked path)."""
        built_pages = int(built_pages)
        return (self.is_prefix()
                and self.prefix_len() == built_pages
                and self.max_entry_page < built_pages)

    # ---- mutations (each bumps version; device views re-derive) -------
    def set_pages(self, pages) -> None:
        pages = np.asarray(pages, np.int64)
        if pages.size:
            self.built[pages] = True
            self.max_entry_page = max(self.max_entry_page,
                                      int(pages.max()))
            self.version += 1

    def clear_pages(self, pages) -> None:
        pages = np.asarray(pages, np.int64)
        if pages.size:
            self.built[pages] = False
            self.version += 1

    def uncovered_pages(self, full_pages: int) -> np.ndarray:
        """Unbuilt pages among the fully-populated [0, full_pages)
        (the only pages eligible for a bit -- the watermark rule)."""
        return np.flatnonzero(~self.built[: int(full_pages)])

    # ---- cached device views -----------------------------------------
    def _memo(self, key, build):
        hit = self._cache.get(key)
        if hit is not None and hit[0] == self.version:
            return hit[1]
        val = build()
        self._cache[key] = (self.version, val)
        return val

    def global_mask(self) -> jax.Array:
        """(n_pages,) bool device mask over global page ids."""
        return self._memo(("global",),
                          lambda: jnp.asarray(self.built))

    def local_built(self, n_shards: int, max_pages: int) -> np.ndarray:
        """(S, max_pages) bool host bitmap over round-robin LOCAL page
        ids (global page p -> shard p % S, local page p // S), padded
        with False (padding pages are never covered)."""
        S = int(n_shards)
        out = np.zeros((S, int(max_pages)), bool)
        for s in range(S):
            loc = self.built[s::S]
            out[s, : loc.shape[0]] = loc
        return out

    def stacked_mask(self, n_shards: int, max_pages: int) -> jax.Array:
        """(S, max_pages) bool device mask (stacked-shard layout)."""
        return self._memo(
            ("stacked", n_shards, max_pages),
            lambda: jnp.asarray(self.local_built(n_shards, max_pages)))

    def packed_words(self, n_shards: int, max_pages: int) -> jax.Array:
        """(S, W) int32 packed little-endian coverage words over local
        page ids -- the Pallas kernels' scalar-prefetch operand.  Bit
        ``p & 31`` of word ``p >> 5`` is page p's built flag (int32:
        the sign bit carries page 31 of each word; arithmetic shifts
        still extract it exactly)."""

        def build():
            loc = self.local_built(n_shards, max_pages)
            W = -(-loc.shape[1] // COVERAGE_WORD_BITS)
            pad = W * COVERAGE_WORD_BITS - loc.shape[1]
            bits = np.pad(loc, ((0, 0), (0, pad))).astype(np.uint32)
            words = bits.reshape(loc.shape[0], W, COVERAGE_WORD_BITS)
            weights = (np.uint32(1) << np.arange(COVERAGE_WORD_BITS,
                                                 dtype=np.uint32))
            packed = (words * weights[None, None, :]).sum(
                axis=2, dtype=np.uint32)
            return jnp.asarray(packed.astype(np.int32))

        return self._memo(("words", n_shards, max_pages), build)

    def view(self, n_shards: int, max_pages: int) -> "CoverageView":
        """Freeze the bitmap into the immutable bundle plans pin.

        ``built_host`` is a *copy* (``set_pages`` mutates the live
        numpy array in place between bursts); the device arrays are
        immutable so the memoized views are shared safely.
        """
        return self._memo(
            ("view", n_shards, max_pages),
            lambda: CoverageView(
                prefix_len=self.prefix_len(),
                count=self.count(),
                built_host=self.built.copy(),
                mask=self.stacked_mask(n_shards, max_pages),
                words=self.packed_words(n_shards, max_pages)))


class CoverageView(NamedTuple):
    """Immutable coverage snapshot pinned into a ``ScanPlan``.

    All burst plans are minted before any dispatch or drain runs, so a
    view taken at plan time is consistent for the whole burst even
    though crack adoption mutates the live bitmap during replay.
    Accounting (pages_scanned / start_page / per-shard pages) is
    computed host-side from ``built_host``; the device ``mask`` /
    ``words`` feed the jitted stitches and the Pallas kernels.
    """
    prefix_len: int          # leading all-built run (start_page report)
    count: int               # total built pages
    built_host: "np.ndarray"  # (n_pages_global,) bool, host copy
    mask: jax.Array          # (S, max_pages) bool, local page ids
    words: jax.Array         # (S, W) int32 packed coverage words


def eligible_global_pages(table) -> np.ndarray:
    """Global ids of fully-populated pages -- the only pages eligible
    for a coverage bit (the watermark page is always table-scanned).

    Sharded storage: each shard's local full prefix maps to global ids
    ``s + S*l`` under the round-robin layout.  Plain: ``[0, full)``.
    """
    psz = table.page_size
    if isinstance(table, ShardedTable):
        S = table.n_shards
        parts = [s + S * np.arange(int(sh.n_rows) // psz, dtype=np.int64)
                 for s, sh in enumerate(table.shards)]
        out = np.concatenate(parts) if parts else np.zeros(0, np.int64)
        out.sort()
        return out
    return np.arange(int(table.n_rows) // psz, dtype=np.int64)


def coverage_from_state(state, table) -> PageCoverage:
    """Seed a bitmap equivalent to an index state's built prefix.

    Global prefixes map directly; per-shard prefixes (shard-targeted
    quanta) map each shard's local run to global ids ``s + S*l``.  The
    result satisfies ``legacy_prefix_ok`` iff the per-shard prefixes
    happen to form a global prefix -- otherwise scans route masked,
    which is exactly the semantics the per-shard stitch implemented.
    """
    if isinstance(state, ShardedIndex):
        S = len(state.shards)
        # The global grid spans S * max(local pages): gpg = pg*S + s
        # can reach that bound on ragged layouts (padding bits simply
        # stay unbuilt, exactly like padding pages stay invisible).
        n_pages = S * max(t.n_pages for t in table.shards)
        cov = PageCoverage(n_pages, table.page_size)
        pages = []
        for s, ix in enumerate(state.shards):
            built = int(ix.built_pages)
            if built > 0:
                pages.append(s + S * np.arange(built, dtype=np.int64))
        if pages:
            cov.set_pages(np.concatenate(pages))
        return cov
    return PageCoverage.from_prefix(table.n_pages,
                                    int(state.built_pages),
                                    table.page_size)


# ---------------------------------------------------------------------------
# Explicit-page builds (crack adoption + hot-range quanta)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("key_attrs", "max_pages"))
def build_pages_at(index: AdHocIndex, table: Table, key_attrs: tuple,
                   page_ids, max_pages: int) -> AdHocIndex:
    """Index an explicit page list (out of order), leaving the
    ``built_pages`` prefix watermark untouched.

    ``page_ids`` is (max_pages,) int32 with -1 padding.  Callers must
    pass only fully-populated, not-yet-covered pages (the coverage
    bitmap is the dedup authority -- double-building a page would
    duplicate its entries).  The pages are gathered from the key
    planes alone, and merged into the index as ``build_pages_vap``
    merges its slice, so per-page work costs are identical.
    """
    pages = jnp.asarray(page_ids, jnp.int32)
    pages_c = jnp.clip(pages, 0, table.n_pages - 1)
    cols = [table.data[:, :, a][pages_c] for a in key_attrs]
    return _merge_pages(index, pages_c, pages >= 0, cols,
                        table.begin_ts[pages_c])


def _pad_page_list(pages: Sequence[int]) -> Tuple[np.ndarray, int]:
    """Pad a host page list to the next power of two (bounds the jit
    cache of ``build_pages_at`` to O(log max_pages) entries)."""
    n = len(pages)
    cap = 1
    while cap < n:
        cap *= 2
    out = np.full((cap,), -1, np.int32)
    out[:n] = np.asarray(pages, np.int32)
    return out, cap


def build_page_list(state, table, key_attrs: tuple, global_pages):
    """Build entries for an explicit GLOBAL page list on either storage
    layout; returns the new index state.  Sharded storage routes each
    page to its round-robin owner (global page p -> shard p % S, local
    page p // S).  The caller flips the coverage bits."""
    global_pages = [int(p) for p in global_pages]
    if not global_pages:
        return state
    if isinstance(state, ShardedIndex):
        S = len(state.shards)
        shards = list(state.shards)
        for s in range(S):
            local = [p // S for p in global_pages if p % S == s]
            if not local:
                continue
            padded, cap = _pad_page_list(local)
            shards[s] = build_pages_at(shards[s], table.shards[s],
                                       key_attrs, padded, max_pages=cap)
        return ShardedIndex(tuple(shards))
    padded, cap = _pad_page_list(global_pages)
    return build_pages_at(state, table, key_attrs, padded, max_pages=cap)


# ---------------------------------------------------------------------------
# Duck-typing helpers (planner/catalog code handles either storage)
# ---------------------------------------------------------------------------

def vbp_n_entries(state) -> jax.Array:
    """Entry count of a VbpState or ShardedVbpState."""
    return state.index.n_entries if isinstance(state, VbpState) \
        else state.n_entries


# ---------------------------------------------------------------------------
# Index range scan (shared by all schemes)
# ---------------------------------------------------------------------------

def index_range_scan(index: AdHocIndex, lo: KeyPair, hi: KeyPair):
    """Return (entry_mask, rids) for composite keys in [lo, hi].

    ``entry_mask`` is (capacity,) bool over the sorted entry array;
    callers gather rows via ``rids`` and must re-check the predicate
    and MVCC visibility against the table (stored keys can be stale
    for updated rows; see ``core.hybrid_scan``).
    """
    ar = jnp.arange(index.capacity, dtype=jnp.int32)
    mask = keys_in_range(index.key_hi, index.key_lo, lo, hi)
    mask &= ar < index.n_entries
    return mask, index.rids
