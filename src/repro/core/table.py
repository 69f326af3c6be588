"""Paged, in-memory columnar table with lightweight multi-versioning.

This is the storage substrate the paper's index tuner operates on
(Section III of the paper).  The table is a fixed-capacity, paged
column store held in JAX arrays so that scans, predicate evaluation
and aggregation are jit-compiled vectorised programs.

Layout
------
``data``      (n_pages, page_size, n_attrs) int32   -- attribute values
``begin_ts``  (n_pages, page_size) int32            -- MVCC begin timestamp
``end_ts``    (n_pages, page_size) int32            -- MVCC end timestamp
``n_rows``    ()                  int32             -- append watermark

A *rid* (row identifier) is ``page_id * page_size + slot``.  Pages are
filled in rid order; inserts and MVCC update-versions are appended at
the ``n_rows`` watermark, exactly like the append-only version chains
of DBMS-X described in the paper (Section III, "Concurrency Control &
Updates").  Old versions are terminated by setting ``end_ts``.

A row version is *visible* to a snapshot timestamp ``ts`` iff::

    begin_ts <= ts < end_ts

Unoccupied slots have ``begin_ts == INT32_MAX`` so they are never
visible.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INF_TS = np.int32(2**31 - 1)  # "infinity" end timestamp (live version)
NEVER_TS = np.int32(2**31 - 1)  # begin_ts for unoccupied slots


class Table(NamedTuple):
    """Paged column store (a pytree).  Reads are functional; the write
    programs donate the planes (see the ownership rule at the mutators)."""

    data: jax.Array      # (n_pages, page_size, n_attrs) int32
    begin_ts: jax.Array  # (n_pages, page_size) int32
    end_ts: jax.Array    # (n_pages, page_size) int32
    n_rows: jax.Array    # () int32 append watermark

    # ---- static geometry helpers -------------------------------------
    @property
    def n_pages(self) -> int:
        return self.data.shape[0]

    @property
    def page_size(self) -> int:
        return self.data.shape[1]

    @property
    def n_attrs(self) -> int:
        return self.data.shape[2]

    @property
    def capacity(self) -> int:
        return self.n_pages * self.page_size


def make_table(n_pages: int, page_size: int, n_attrs: int) -> Table:
    """An empty table with fixed capacity."""
    return Table(
        data=jnp.zeros((n_pages, page_size, n_attrs), jnp.int32),
        begin_ts=jnp.full((n_pages, page_size), NEVER_TS, jnp.int32),
        end_ts=jnp.full((n_pages, page_size), INF_TS, jnp.int32),
        n_rows=jnp.zeros((), jnp.int32),
    )


def load_table(values: np.ndarray, page_size: int, n_pages: int | None = None,
               ts: int = 0) -> Table:
    """Bulk-load ``values`` (n, n_attrs) into a fresh table at timestamp ts.

    ``n_pages`` may reserve extra append room for inserts/updates; it
    defaults to exactly fitting the data.
    """
    values = np.asarray(values, np.int32)
    n, n_attrs = values.shape
    min_pages = -(-n // page_size)
    if n_pages is None:
        n_pages = min_pages
    if n_pages < min_pages:
        raise ValueError(f"n_pages={n_pages} cannot hold {n} rows")
    data = np.zeros((n_pages, page_size, n_attrs), np.int32)
    begin = np.full((n_pages, page_size), NEVER_TS, np.int32)
    end = np.full((n_pages, page_size), INF_TS, np.int32)
    flat = data.reshape(-1, n_attrs)
    flat[:n] = values
    begin.reshape(-1)[:n] = ts
    return Table(jnp.asarray(data), jnp.asarray(begin), jnp.asarray(end),
                 jnp.asarray(n, jnp.int32))


# ---------------------------------------------------------------------------
# Visibility & predicates
# ---------------------------------------------------------------------------

def visible_mask(table: Table, ts) -> jax.Array:
    """(n_pages, page_size) bool -- versions visible at snapshot ``ts``."""
    ts = jnp.asarray(ts, jnp.int32)
    return (table.begin_ts <= ts) & (ts < table.end_ts)


def range_predicate_mask(table: Table, attr: int, lo, hi) -> jax.Array:
    """(n_pages, page_size) bool -- rows with lo <= a_attr <= hi (inclusive)."""
    col = table.data[:, :, attr]
    return (col >= jnp.asarray(lo, jnp.int32)) & (col <= jnp.asarray(hi, jnp.int32))


def conj_predicate_mask(table: Table, attrs, los, his) -> jax.Array:
    """Conjunctive multi-attribute range predicate.

    ``attrs`` is a static tuple of column indices; ``los``/``his`` are
    (possibly traced) per-attribute inclusive bounds.
    """
    mask = jnp.ones(table.data.shape[:2], bool)
    for k, attr in enumerate(attrs):
        mask &= range_predicate_mask(table, attr, los[k], his[k])
    return mask


# ---------------------------------------------------------------------------
# Mutators (INSERT / UPDATE) -- in place on the planes, fixed shapes
# ---------------------------------------------------------------------------
#
# Ownership rule: ``insert_rows`` and ``update_rows`` donate the three
# planes of the table they are given (``data``, ``begin_ts``,
# ``end_ts``).  XLA writes the touched rows into those buffers, the
# returned table holds them, and the given table's planes are deleted.
# Its ``n_rows`` scalar is not donated: the returned table carries a new
# one.  So a caller passes only a table it owns, one nothing else reads
# afterwards.  A table shared with anyone else (a loaded table, one
# another database holds) is first copied once by ``copy_table``, its
# own program; ``Database`` keeps that rule (core/executor.py).


def _write_program(name: str, **jit_kwargs):
    """``jax.jit`` a write program of ``(planes, n_rows, ...)`` with
    ``planes`` donated, named ``name`` in the compiled module and the
    profiler's trace (``jit_<name>``)."""

    def wrap(fun):
        fun.__name__ = fun.__qualname__ = name
        return jax.jit(fun, donate_argnums=(0,), **jit_kwargs)

    return wrap


def _write_rows(data, base, rows, ok):
    """``data`` with ``rows[i]`` at rid ``base + i`` where ``ok[i]``.
    The rows are consecutive, so they lie in the few pages from
    ``base``'s: those pages are read, the rows put in, and the pages
    written back in place.  A scatter of the rows themselves would
    relayout (row-wise) or flatten (per cell) the whole
    attribute-major plane through a table-sized temporary."""
    n_pages, psz, _ = data.shape
    m = rows.shape[0]
    k = min(-(-m // psz) + 1, n_pages)
    start = jnp.minimum(base // psz, n_pages - k)
    pages = jax.lax.dynamic_slice_in_dim(data, start, k, axis=0)
    local = jnp.where(ok, base + jnp.arange(m, dtype=jnp.int32) - start * psz,
                      k * psz)  # past the pages: dropped
    pages = pages.reshape(k * psz, -1).at[local].set(rows, mode="drop")
    return jax.lax.dynamic_update_slice_in_dim(
        data, pages.reshape(k, psz, -1), start, axis=0)


def _append(table: Table, rows: jax.Array, ts, n_new) -> Table:
    ts = jnp.asarray(ts, jnp.int32)
    base = table.n_rows
    idx = base + jnp.arange(rows.shape[0], dtype=jnp.int32)
    ok = (jnp.arange(rows.shape[0]) < n_new) & (idx < table.capacity)
    # Rows not written go past the last page, where the scatters drop
    # them: on a real slot they would race the row written there.
    idx = jnp.where(ok, idx, table.capacity)
    pg, sl = idx // table.page_size, idx % table.page_size
    data = _write_rows(table.data, base, rows.astype(jnp.int32), ok)
    begin = table.begin_ts.at[pg, sl].set(ts, mode="drop")
    end = table.end_ts.at[pg, sl].set(INF_TS, mode="drop")
    n_rows = jnp.minimum(base + jnp.asarray(n_new, jnp.int32),
                         jnp.asarray(table.capacity, jnp.int32))
    return Table(data, begin, end, n_rows)


@_write_program("insert_rows", static_argnames=("max_new",))
def _insert_program(planes, n_rows, rows, ts, n_new, max_new):
    del max_new  # shape is static via rows
    return _append(Table(*planes, n_rows), rows, ts, n_new)


def first_matches(match: jax.Array, max_new: int):
    """The first ``max_new`` True entries of the (n_pages, page_size)
    mask ``match``, in rid order, found from per-page counts: entry k
    lies in the first page whose running count of matches exceeds k, at
    the slot of its rank among that page's matches.  Its cost follows
    the pages and the ``max_new`` rows gathered, not the capacity.

    Returns (rids, n_match): ``rids[k]`` is ``np.flatnonzero(match)[k]``
    for every k < ``min(n_match, max_new)``; every later entry is the
    capacity, one past the last slot, so a scatter with ``mode="drop"``
    skips it and no real slot is written twice."""
    n_pages, psz = match.shape
    ends = jnp.cumsum(jnp.sum(match, axis=1, dtype=jnp.int32))
    k = jnp.arange(max_new, dtype=jnp.int32)[:, None]
    before = ends[None, :] <= k  # (max_new, n_pages): pages wholly before k
    page = jnp.sum(before, axis=1, dtype=jnp.int32)  # n_pages past the matches
    rank = k[:, 0] - jnp.max(jnp.where(before, ends[None, :], 0), axis=1)
    row = jnp.cumsum(match[jnp.minimum(page, n_pages - 1)], axis=1,
                     dtype=jnp.int32)
    slot = jnp.sum(row <= rank[:, None], axis=1, dtype=jnp.int32)
    rids = jnp.where(page < n_pages, page * psz + slot, n_pages * psz)
    return rids, ends[-1]


@_write_program("update_rows", static_argnames=("attrs", "max_new"))
def _update_program(planes, n_rows, attrs, los, his, set_attrs, set_vals,
                    ts, max_new):
    table = Table(*planes, n_rows)
    ts = jnp.asarray(ts, jnp.int32)
    set_attrs = jnp.asarray(set_attrs, jnp.int32)
    set_vals = jnp.asarray(set_vals, jnp.int32)
    match = conj_predicate_mask(table, attrs, los, his) & visible_mask(table, ts)
    rids, n_match = first_matches(match, max_new)
    pg, sl = rids // table.page_size, rids % table.page_size

    # Terminate old versions; entries past the matches fall past the
    # last page and are dropped.
    end = table.end_ts.at[pg, sl].set(ts, mode="drop")
    # (max_new, n_attrs), gathered cell by cell: a row-wise gather makes
    # XLA move the whole attribute-major ``data`` plane into a row-major
    # layout first.
    attrs_all = jnp.arange(table.n_attrs, dtype=jnp.int32)
    pg_in = jnp.minimum(pg, table.n_pages - 1)
    old_rows = table.data[pg_in[:, None], sl[:, None], attrs_all[None, :]]
    new_rows = old_rows.at[:, set_attrs].set(
        jnp.broadcast_to(set_vals, (old_rows.shape[0], set_vals.shape[0])))
    table = Table(table.data, table.begin_ts, end, table.n_rows)
    n_upd = jnp.minimum(n_match, max_new)
    return _append(table, new_rows, ts, n_upd), n_upd


def insert_rows(table: Table, rows: jax.Array, ts, n_new, max_new: int) -> Table:
    """Append ``n_new`` of the first ``max_new`` rows at timestamp ts.

    ``rows`` is (max_new, n_attrs); only the first n_new are live.
    Appends past capacity are dropped (callers size tables to avoid it).
    Writes in place: ``table``'s planes are donated to the returned
    table, so the caller must own ``table`` (the ownership rule above).
    """
    return _insert_program(table[:3], table.n_rows, rows, ts, n_new,
                           max_new=max_new)


def update_rows(table: Table, attrs: tuple, los, his, set_attrs,
                set_vals, ts, max_new: int) -> Tuple[Table, jax.Array]:
    """MVCC UPDATE: terminate matching visible versions and append new ones.

    Matches rows where the conjunctive range predicate over ``attrs``
    holds, sets columns ``set_attrs`` (a *dynamic* int32 index array,
    so randomised SET lists do not trigger recompilation) to
    ``set_vals`` in the new versions.  At most ``max_new`` new versions
    are materialised per call (the paper's update templates touch small
    row counts; the cap keeps shapes static): the first ``max_new``
    matches in rid order, found from per-page match counts
    (``first_matches``), with no sort of the capacity.  Selection
    entries past the matches point one past the last slot, where the
    ``end_ts`` scatter drops them.  Returns
    (new_table, n_updated).  Writes in place: ``table``'s planes are
    donated to ``new_table``, so the caller must own ``table`` (the
    ownership rule above).
    """
    return _update_program(table[:3], table.n_rows, attrs, los, his,
                           set_attrs, set_vals, ts, max_new=max_new)


@jax.jit
def copy_table(table: Table) -> Table:
    """``table`` in new plane buffers, for a writer that does not own it:
    one read and one write of each plane.  ``n_rows`` is shared, as no
    write donates it."""
    return Table(jnp.copy(table.data), jnp.copy(table.begin_ts),
                 jnp.copy(table.end_ts), table.n_rows)


def table_bytes(table: Table) -> int:
    """Bytes of the planes a copy of ``table`` writes."""
    return sum(int(x.size) * x.dtype.itemsize for x in table[:3])


# ---------------------------------------------------------------------------
# Full table scan (the fallback access path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("attrs", "agg_attr", "from_page_static"))
def table_scan(table: Table, attrs: tuple, los, his, ts, agg_attr: int,
               from_page=0, from_page_static: bool = False):
    """Scan pages >= from_page, returning (match_mask, sum, count).

    ``match_mask`` is (n_pages, page_size) and already accounts for
    MVCC visibility.  ``from_page`` supports the hybrid scan's partial
    table scan.
    """
    del from_page_static
    mask = conj_predicate_mask(table, attrs, los, his) & visible_mask(table, ts)
    page_ids = jnp.arange(table.n_pages, dtype=jnp.int32)[:, None]
    mask = mask & (page_ids >= jnp.asarray(from_page, jnp.int32))
    vals = table.data[:, :, agg_attr]
    # int32 accumulation with wraparound semantics (x64 is disabled in
    # this deployment; oracles in tests use matching np.int32 math).
    s = jnp.sum(jnp.where(mask, vals, 0), dtype=jnp.int32)
    c = jnp.sum(mask, dtype=jnp.int32)
    return mask, s, c


def rid_page(rid, page_size: int):
    return rid // page_size


def rid_slot(rid, page_size: int):
    return rid % page_size


# ---------------------------------------------------------------------------
# Sharded storage: pages partitioned round-robin across a shard list
# ---------------------------------------------------------------------------
#
# Global page ``p`` lives on shard ``p % S`` at local page ``p // S``.
# Round-robin (not range) partitioning is load-bearing: the engine's
# in-order VAP build walks global pages 0,1,2,..., so under round-robin
# the globally built *prefix* [0, m) maps to a locally built prefix on
# every shard -- which is exactly the invariant the hybrid scan's
# stitch point relies on (see index.sharded_build_pages_vap).  Rows
# keep their global rids; a shard's slots therefore fill in local rid
# order and each shard is itself a well-formed ``Table`` with a local
# append watermark, so every single-table operator applies per shard
# unchanged.
#
# Contract (tested in tests/test_sharded_engine.py): for any shard
# count, query results and all accounting are bit-identical to the
# single-shard engine.  Sums stay int32 -- two's-complement addition is
# associative and commutative, so per-shard partial sums reduce to the
# exact single-shard value in any tree order.


class ShardedTable(NamedTuple):
    """Paged column store partitioned round-robin over page id.

    ``shards`` are plain Tables holding local pages; ``n_rows`` is the
    *global* append watermark (each shard additionally tracks its local
    watermark, kept consistent by the sharded mutators).  The geometry
    properties report global values so planner/cost code written
    against ``Table`` works on either storage unchanged.
    """

    shards: Tuple[Table, ...]
    n_rows: jax.Array          # () int32 global append watermark

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def page_size(self) -> int:
        return self.shards[0].page_size

    @property
    def n_attrs(self) -> int:
        return self.shards[0].n_attrs

    @property
    def n_pages(self) -> int:
        return sum(t.n_pages for t in self.shards)

    @property
    def capacity(self) -> int:
        return self.n_pages * self.page_size


def local_n_rows(n_rows, shard: int, n_shards: int, page_size: int,
                 local_pages: int) -> jax.Array:
    """Local append watermark implied by the global watermark.

    Global rids fill pages in order, so a shard's occupied slots are
    exactly: its pages fully below the global watermark page, plus the
    watermark page's partial fill if this shard owns it.
    """
    n = jnp.asarray(n_rows, jnp.int32)
    watermark = n // page_size          # number of complete global pages
    partial = n % page_size
    full_local = jnp.clip((watermark - shard + n_shards - 1) // n_shards,
                          0, local_pages)
    owns = ((watermark % n_shards) == shard) & \
        ((watermark // n_shards) < local_pages)
    return (full_local * page_size + jnp.where(owns, partial, 0)
            ).astype(jnp.int32)


def global_rids(local_pages: int, shard: int, n_shards: int,
                page_size: int) -> jax.Array:
    """(local_pages * page_size,) global rid of each local flat slot."""
    pages = jnp.arange(local_pages, dtype=jnp.int32) * n_shards + shard
    slots = jnp.arange(page_size, dtype=jnp.int32)
    return (pages[:, None] * page_size + slots[None, :]).reshape(-1)


def shard_table(table: Table, num_shards: int) -> ShardedTable:
    """Partition ``table`` round-robin by page id into ``num_shards``."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if table.n_pages < num_shards:
        raise ValueError(f"cannot spread {table.n_pages} pages over "
                         f"{num_shards} shards")
    shards = []
    for s in range(num_shards):
        data = table.data[s::num_shards]
        shards.append(Table(
            data=data,
            begin_ts=table.begin_ts[s::num_shards],
            end_ts=table.end_ts[s::num_shards],
            n_rows=local_n_rows(table.n_rows, s, num_shards,
                                table.page_size, data.shape[0])))
    return ShardedTable(tuple(shards), jnp.asarray(table.n_rows, jnp.int32))


def round_robin_layout(st: ShardedTable) -> bool:
    """True iff the occupied pages follow the round-robin page map
    (the layout ``shard_table`` produces): each shard's fully
    populated pages are exactly its share of one global page prefix,
    and at most the global watermark page is partially filled.

    Adopted pre-sharded tables may violate this (range/tenant
    partitioning with skewed shard sizes); the planner then routes
    hybrid scans through the per-shard stitch, whose soundness does
    not depend on the global prefix invariant.
    """
    S = st.n_shards
    psz = st.page_size
    rows = [int(t.n_rows) for t in st.shards]
    full = [r // psz for r in rows]
    total_full = sum(full)
    for s, f in enumerate(full):
        if f != max(0, -(-(total_full - s) // S)):
            return False
    partial = [s for s, r in enumerate(rows) if r % psz]
    return not partial or partial == [total_full % S]


def unshard_table(st: ShardedTable) -> Table:
    """Reassemble the logical table (test oracle / resharding)."""
    S = st.n_shards
    t0 = st.shards[0]
    data = jnp.zeros((st.n_pages, t0.page_size, t0.n_attrs), jnp.int32)
    begin = jnp.zeros((st.n_pages, t0.page_size), jnp.int32)
    end = jnp.zeros((st.n_pages, t0.page_size), jnp.int32)
    for s, t in enumerate(st.shards):
        data = data.at[s::S].set(t.data)
        begin = begin.at[s::S].set(t.begin_ts)
        end = end.at[s::S].set(t.end_ts)
    return Table(data, begin, end, jnp.asarray(st.n_rows, jnp.int32))


# ---------------------------------------------------------------------------
# Stacked shard pytree: the fused single-dispatch layout
# ---------------------------------------------------------------------------
#
# The engine's batched sharded scans do not loop over shards any more:
# every shard's column planes are stacked on one leading axis (padded
# to a uniform local page grid) so one vmapped program -- or one
# Pallas launch with a shard grid axis -- covers every shard.  Padding
# pages carry ``begin_ts == NEVER_TS``, so they are invisible to every
# snapshot and contribute exact int32 zeros to every aggregate; the
# real per-shard geometry travels alongside (``local_pages`` and each
# shard's local ``n_rows`` watermark), so accounting never sees the
# padding.
#
# The stack is cached per ``ShardedTable.shards`` tuple *identity*:
# every mutator (``sharded_insert_rows`` / ``sharded_update_rows``)
# and ``Database.reshard`` builds a fresh shards tuple, so a mutation
# is automatically a cache miss -- functional invalidation.  Entries
# keep a strong reference to their key tuple, which also makes the
# id() key collision-proof while the entry lives.


class StackedShards(NamedTuple):
    """All shards of one ``ShardedTable`` on a leading shard axis.

    ``table`` is a ``Table`` pytree whose leaves carry the extra
    leading (S,) axis (``n_rows`` holds the per-shard local
    watermarks); slicing shard ``s`` off every leaf yields that
    shard's exact padded Table, so per-shard operators vmap over it
    unchanged.  NOTE: ``Table``'s geometry properties read the wrong
    axes on the stacked leaves -- use ``shard_ids``/``local_pages``
    and the owning ``ShardedTable`` for geometry instead.
    """

    table: Table  # leaves: (S, max_pages, page_size[, n_attrs]) / (S,)
    shard_ids: jax.Array  # (S,) int32
    local_pages: jax.Array  # (S,) int32 pre-padding page counts


_STACK_CACHE: OrderedDict = OrderedDict()  # id(shards) -> (shards, stacked)
# Each entry pins its shards tuple AND a padded copy (~2x one table).
# The cap only needs to cover the tables live in one Database (scan
# fan-outs always hit the newest tuple per table; older generations
# are dead weight), so keep it tight: mutation-heavy workloads would
# otherwise pin MAX dead table generations.
_STACK_CACHE_MAX = 4


def _same_tuple(a: tuple, b: tuple) -> bool:
    return len(a) == len(b) and all(x is y for x, y in zip(a, b))


def identity_lru_lookup(cache: OrderedDict, max_entries: int,
                        key_tuple: tuple, build):
    """Identity-keyed LRU shared by the stack caches (this module and
    ``index.stacked_shard_indexes``): the entry key is the *identity*
    of ``key_tuple``'s elements, and every entry pins its key tuple so
    an id() can never be reused while the entry lives.  ``build`` is
    called on a miss."""
    key = id(key_tuple)
    hit = cache.get(key)
    if hit is not None and _same_tuple(hit[0], key_tuple):
        cache.move_to_end(key)
        return hit[1]
    value = build()
    cache[key] = (key_tuple, value)
    while len(cache) > max_entries:
        cache.popitem(last=False)
    return value


def _stack_shards(st: ShardedTable) -> StackedShards:
    max_pages = max(t.n_pages for t in st.shards)

    def padp(x, fill):
        pad = max_pages - x.shape[0]
        if pad == 0:
            return x
        widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=fill)

    table = Table(
        data=jnp.stack([padp(t.data, 0) for t in st.shards]),
        begin_ts=jnp.stack([padp(t.begin_ts, NEVER_TS) for t in st.shards]),
        end_ts=jnp.stack([padp(t.end_ts, INF_TS) for t in st.shards]),
        n_rows=jnp.stack([jnp.asarray(t.n_rows, jnp.int32)
                          for t in st.shards]),
    )
    return StackedShards(
        table=table,
        shard_ids=jnp.arange(st.n_shards, dtype=jnp.int32),
        local_pages=jnp.asarray([t.n_pages for t in st.shards], jnp.int32),
    )


def stacked_shards(st: ShardedTable) -> StackedShards:
    """Cached stacked/padded pytree for ``st`` (see the section note:
    mutators and reshard rebuild the shards tuple, so identity keying
    doubles as invalidation)."""
    return identity_lru_lookup(_STACK_CACHE, _STACK_CACHE_MAX, st.shards,
                               lambda: _stack_shards(st))


@functools.partial(jax.jit, static_argnames=("max_new",))
def sharded_insert_rows(st: ShardedTable, rows: jax.Array, ts, n_new,
                        max_new: int) -> ShardedTable:
    """Sharded INSERT: same append-at-watermark semantics as
    ``insert_rows``; each row is scattered to the shard owning its
    global page.  Rows not written go past the shard's last page, where
    the scatters drop them, as in the single-table op."""
    del max_new
    S = len(st.shards)
    psz = st.page_size
    capacity = st.capacity
    ts = jnp.asarray(ts, jnp.int32)
    base = st.n_rows
    idx = base + jnp.arange(rows.shape[0], dtype=jnp.int32)
    ok = (jnp.arange(rows.shape[0]) < n_new) & (idx < capacity)
    idx = jnp.where(ok, idx, capacity - 1)
    gp, sl = idx // psz, idx % psz
    owner, lp = gp % S, gp // S
    n_rows = jnp.minimum(base + jnp.asarray(n_new, jnp.int32),
                         jnp.asarray(capacity, jnp.int32))
    new_shards = []
    for s, t in enumerate(st.shards):
        ok_s = ok & (owner == s)
        lp_s = jnp.where(ok_s, lp, t.n_pages)  # dropped past the last page
        data = t.data.at[lp_s, sl].set(rows.astype(jnp.int32), mode="drop")
        begin = t.begin_ts.at[lp_s, sl].set(ts, mode="drop")
        end = t.end_ts.at[lp_s, sl].set(INF_TS, mode="drop")
        new_shards.append(Table(data, begin, end,
                                local_n_rows(n_rows, s, S, psz, t.n_pages)))
    return ShardedTable(tuple(new_shards), n_rows)


@functools.partial(jax.jit, static_argnames=("attrs", "max_new"))
def sharded_update_rows(st: ShardedTable, attrs: tuple, los, his, set_attrs,
                        set_vals, ts, max_new: int
                        ) -> Tuple[ShardedTable, jax.Array]:
    """Sharded MVCC UPDATE, bit-identical to ``update_rows``.

    The "first max_new matches in global rid order" selection cannot be
    made per shard (matches interleave across shards in rid order), so
    per-shard match masks are scattered into one global
    (pages, page_size) mask and ``first_matches`` selects on it exactly
    as the single-table op does; the chosen rids are then routed back
    to their owning shards for version termination and the gather of
    pre-image rows.  Entries past the matches, and entries another
    shard owns, fall past a shard's last page, where its ``end_ts``
    scatter drops them.
    """
    S = len(st.shards)
    psz = st.page_size
    capacity = st.capacity
    ts = jnp.asarray(ts, jnp.int32)
    set_attrs = jnp.asarray(set_attrs, jnp.int32)
    set_vals = jnp.asarray(set_vals, jnp.int32)

    flat_match = jnp.zeros((capacity,), bool)
    for s, t in enumerate(st.shards):
        m = conj_predicate_mask(t, attrs, los, his) & visible_mask(t, ts)
        rid_map = global_rids(t.n_pages, s, S, psz)
        flat_match = flat_match.at[rid_map].set(m.reshape(-1))
    rids, n_match = first_matches(flat_match.reshape(-1, psz), max_new)
    sel_ok = rids < capacity
    gp, sl = rids // psz, rids % psz
    owner, lp = gp % S, gp // S

    old_rows = jnp.zeros((max_new, st.n_attrs), jnp.int32)
    new_shards = []
    for s, t in enumerate(st.shards):
        own_s = owner == s
        lp_s = jnp.where(sel_ok & own_s, lp, t.n_pages)  # dropped
        end = t.end_ts.at[lp_s, sl].set(ts, mode="drop")
        vals = t.data[jnp.where(own_s, lp, 0).clip(0, t.n_pages - 1),
                      jnp.where(own_s, sl, 0)]
        old_rows = jnp.where(own_s[:, None], vals, old_rows)
        new_shards.append(Table(t.data, t.begin_ts, end, t.n_rows))
    new_rows = old_rows.at[:, set_attrs].set(
        jnp.broadcast_to(set_vals, (old_rows.shape[0], set_vals.shape[0])))
    n_upd = jnp.minimum(n_match, max_new)
    st = ShardedTable(tuple(new_shards), st.n_rows)
    st = sharded_insert_rows(st, new_rows, ts, n_upd, max_new=max_new)
    return st, n_upd
