"""Pallas TPU kernels: multi-query fused filter+aggregate table scans.

One launch evaluates a whole *batch* of conjunctive filter+aggregate
queries over the same column planes; a single query is a batch of
one.  A launch per query would be launch-bound on read bursts -- every
query would pay a kernel launch plus a fresh HBM -> VMEM stream of the
same columns.  Batching amortises both:

* Grid is ``(page_block, query)`` with the query dimension innermost:
  consecutive grid steps share the same input block, so Pallas keeps
  the block resident in VMEM and streams each column tile from HBM
  once per *batch*, not once per query.
* All per-query parameters -- predicate bounds, MVCC snapshot
  timestamp and the hybrid scan's ``start_page`` -- arrive as one
  scalar-prefetch operand in SMEM, indexed by the query grid
  coordinate.  Scalar prefetch means ``start_page`` is known before
  the block's DMA is issued; a (block, query) step whose pages lie
  entirely inside that query's indexed prefix skips its compute via
  ``pl.when`` (and, when *every* query in the batch skips the block,
  no query forces the DMA).
* Each (block, query) step adds its partial (sum, count) into the
  query's slot of two whole-array ``(n_queries,)`` SMEM outputs,
  zeroed on the first page block.  (A per-step ``(1, 1)`` output
  block breaks the TPU's (8, 128) tiling rule.)  Accumulation stays
  int32 (the engine's documented wraparound semantics), so the
  summation order cannot change the bits.

``sharded_batched_filter_agg`` extends the same design with a leading
*shard* grid axis over stacked column planes (``(S, n_pages,
page_size)``; see ``core.table.stacked_shards``), so a sharded read
burst is ONE launch regardless of shard count:

* Grid is ``(shard, page_block, query)``, query still innermost; each
  (shard, block) tile streams once per batch.
* ``start_pages`` is a per-(shard, query) scalar-prefetch table of
  *local* stitch points -- one layout covers pure full scans (all
  zero), the global hybrid stitch (the global start page mapped into
  each shard's local page space) and the per-shard ``hybrid_ps``
  stitch (each shard's own local stitch point).
* Shards are padded to a uniform page grid.  Padding *pages* carry
  ``begin_ts == INT32_MAX`` so the visibility term masks them off;
  whole padding *blocks* past a shard's last real block are skipped
  pre-DMA exactly like prefix blocks: the index map clamps the block
  coordinate into the shard's [first-needed, last-real] block range,
  so skipped steps revisit a resident block and ``pl.when`` keeps
  them out of the sums.

Semantics contract: ``ref.batched_filter_agg_ref`` -- per query
identical to ``ref.masked_filter_agg_ref``.  A single-shard launch is
bit-identical to the plain batched kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MIN = -(2**31)
I32_MAX = 2**31 - 1

# Per-block VMEM budget for the non-interpret TPU path.  A TPU core
# has ~16 MiB of VMEM; the pipeline double-buffers every input block,
# and outputs / scalar prefetch / kernel scratch need headroom too, so
# the streamed input planes get a 4 MiB slice by default.
TPU_VMEM_BLOCK_BYTES = 4 * 1024 * 1024


def tpu_block_pages(
    n_pages: int,
    page_size: int,
    n_planes: int = 5,
    vmem_budget_bytes: int = TPU_VMEM_BLOCK_BYTES,
) -> int:
    """Page-axis block size for a real-hardware (non-interpret) launch.

    Sizes the block to the chip instead of the fixed interpret-mode
    ladder: the largest power-of-two page count whose ``n_planes``
    streamed int32 planes fit ``vmem_budget_bytes`` *double-buffered*
    (Pallas prefetches block k+1 while k computes, so two copies of
    every input block are resident).  Floor of 8 pages keeps the
    sublane dimension at the int32 minimum tile (8, 128) even for tiny
    tables; page_size is already lane-aligned by the Table layout.
    """
    per_page = int(page_size) * int(n_planes) * 4  # int32 bytes
    limit = max(int(vmem_budget_bytes) // (2 * per_page), 8)
    bp = 8
    while bp * 2 <= min(limit, max(int(n_pages), 8)):
        bp *= 2
    return bp


# Per-query (sum, count) outputs: whole (n_queries,) arrays in SMEM,
# resident for the whole grid, so each step accumulates into its
# query's slot.
_SMEM_OUT = pl.BlockSpec(memory_space=pltpu.SMEM)


def _query_vector(n_queries):
    return jax.ShapeDtypeStruct((n_queries,), jnp.int32)


def _words_spanned(block_pages):
    """Most 32-page coverage words one block of ``block_pages`` pages
    can touch (a block aligned to 32 pages touches exactly its own)."""
    if block_pages % 32 == 0:
        return block_pages // 32
    return -(-block_pages // 32) + 1


def _pad_pages(planes, n_pages, block_pages, page_axis):
    """Pad the page axis up to a whole number of blocks; padding rows
    carry begin_ts = INT32_MAX -> never visible."""
    n_blocks = pl.cdiv(n_pages, block_pages)
    pad = n_blocks * block_pages - n_pages
    if pad:
        fills = (0, 0, 0, I32_MAX, I32_MAX)

        def padp(x, fill):
            widths = [(0, 0)] * x.ndim
            widths[page_axis] = (0, pad)
            return jnp.pad(x, widths, constant_values=fill)

        planes = tuple(padp(x, f) for x, f in zip(planes, fills))
    return planes, n_blocks


def _batched_kernel(
    scalars_ref,
    pred0_ref,
    pred1_ref,
    agg_ref,
    begin_ref,
    end_ref,
    sum_ref,
    cnt_ref,
    *,
    block_pages: int,
):
    """One grid step: reduce a (block_pages, page_size) tile for one
    query of the batch.

    scalars_ref (SMEM, scalar-prefetch) is (7, n_queries) int32 with
    rows [lo0, hi0, lo1, hi1, ts, start_page, first_needed_block]
    (the last row is batch-wide, used only by the input index_map).
    """
    blk = pl.program_id(0)
    q = pl.program_id(1)
    lo0, hi0 = scalars_ref[0, q], scalars_ref[1, q]
    lo1, hi1 = scalars_ref[2, q], scalars_ref[3, q]
    ts = scalars_ref[4, q]
    start_page = scalars_ref[5, q]

    first_page = blk * block_pages

    @pl.when(blk == 0)
    def _init():
        sum_ref[q] = jnp.int32(0)
        cnt_ref[q] = jnp.int32(0)

    @pl.when(first_page + block_pages > start_page)
    def _run():
        p0 = pred0_ref[...]
        p1 = pred1_ref[...]
        ag = agg_ref[...]
        bts = begin_ref[...]
        ets = end_ref[...]
        mask = (p0 >= lo0) & (p0 <= hi0) & (p1 >= lo1) & (p1 <= hi1)
        mask &= (bts <= ts) & (ts < ets)
        # Per-page mask inside a block straddling this query's
        # start_page boundary.
        rows = jax.lax.broadcasted_iota(jnp.int32, p0.shape, 0)
        mask &= (first_page + rows) >= start_page
        sum_ref[q] += jnp.sum(jnp.where(mask, ag, 0), dtype=jnp.int32)
        cnt_ref[q] += jnp.sum(mask, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def batched_filter_agg(
    pred0,
    pred1,
    agg,
    begin_ts,
    end_ts,
    los0,
    his0,
    los1,
    his1,
    tss,
    start_pages,
    block_pages: int = 8,
    interpret: bool = False,
):
    """Multi-query fused filter+aggregate scan.

    Column planes are (n_pages, page_size) int32, shared by every
    query in the batch; per-query operands ``los0/his0/los1/his1/tss/
    start_pages`` are (n_queries,) int32.  Single-attribute queries
    pass los1 = INT32_MIN, his1 = INT32_MAX; full (non-hybrid) scans
    pass start_pages = 0.  Returns (sums, counts), each (n_queries,)
    int32.
    """
    n_pages, page_size = pred0.shape
    n_queries = los0.shape[0]

    planes, n_blocks = _pad_pages(
        (pred0, pred1, agg, begin_ts, end_ts), n_pages, block_pages, 0
    )
    pred0, pred1, agg, begin_ts, end_ts = planes

    # Row 6: first page-block ANY query needs (blocks below it lie in
    # every query's indexed prefix -- they form a skippable prefix).
    start_pages = jnp.asarray(start_pages, jnp.int32)
    first_blk = jnp.minimum(
        jnp.min(start_pages) // block_pages, n_blocks - 1
    )
    scalars = jnp.stack(
        [
            jnp.asarray(v, jnp.int32)
            for v in (
                los0,
                his0,
                los1,
                his1,
                tss,
                start_pages,
                jnp.full((n_queries,), first_blk, jnp.int32),
            )
        ]
    )

    # index_map receives (*grid_indices, *scalar_prefetch_refs); the
    # input block depends only on the page-block coordinate, so the
    # innermost query steps revisit the resident block.  Clamping the
    # coordinate up to the batch-wide first needed block makes the
    # skippable prefix revisit THAT block too, so its DMAs are elided
    # -- the pre-DMA skip (pl.when in the kernel body keeps the prefix
    # out of every query's sums).
    block = pl.BlockSpec(
        (block_pages, page_size),
        lambda i, q, s: (jnp.maximum(i, s[6, 0]), 0),
    )
    kernel = functools.partial(_batched_kernel, block_pages=block_pages)
    sums, cnts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks, n_queries),
            in_specs=[block] * 5,
            out_specs=[_SMEM_OUT, _SMEM_OUT],
        ),
        out_shape=[_query_vector(n_queries), _query_vector(n_queries)],
        interpret=interpret,
    )(scalars, pred0, pred1, agg, begin_ts, end_ts)
    return sums, cnts


def _sharded_kernel(
    qparams_ref,
    starts_ref,
    blocks_ref,
    pred0_ref,
    pred1_ref,
    agg_ref,
    begin_ref,
    end_ref,
    sum_ref,
    cnt_ref,
    *,
    block_pages: int,
):
    """One grid step: reduce one shard's (block_pages, page_size) tile
    for one query of the batch.

    Scalar-prefetch operands (SMEM):
      qparams_ref (5, n_queries)  -- [lo0, hi0, lo1, hi1, ts] rows
      starts_ref  (S, n_queries)  -- per-(shard, query) LOCAL stitch
                                     points (0 = full scan)
      blocks_ref  (S, 2)          -- per-shard [first_needed_block,
                                     last_real_block] (index_map +
                                     padding skip)
    """
    s = pl.program_id(0)
    blk = pl.program_id(1)
    q = pl.program_id(2)
    lo0, hi0 = qparams_ref[0, q], qparams_ref[1, q]
    lo1, hi1 = qparams_ref[2, q], qparams_ref[3, q]
    ts = qparams_ref[4, q]
    start_page = starts_ref[s, q]
    last_blk = blocks_ref[s, 1]

    first_page = blk * block_pages
    live = (first_page + block_pages > start_page) & (blk <= last_blk)

    @pl.when((s == 0) & (blk == 0))
    def _init():
        sum_ref[q] = jnp.int32(0)
        cnt_ref[q] = jnp.int32(0)

    @pl.when(live)
    def _run():
        p0 = pred0_ref[...]
        p1 = pred1_ref[...]
        ag = agg_ref[...]
        bts = begin_ref[...]
        ets = end_ref[...]
        mask = (p0 >= lo0) & (p0 <= hi0) & (p1 >= lo1) & (p1 <= hi1)
        mask &= (bts <= ts) & (ts < ets)
        # Blocks are (1, block_pages, page_size); the page axis is 1.
        rows = jax.lax.broadcasted_iota(jnp.int32, p0.shape, 1)
        mask &= (first_page + rows) >= start_page
        sum_ref[q] += jnp.sum(jnp.where(mask, ag, 0), dtype=jnp.int32)
        cnt_ref[q] += jnp.sum(mask, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def sharded_batched_filter_agg(
    pred0,
    pred1,
    agg,
    begin_ts,
    end_ts,
    los0,
    his0,
    los1,
    his1,
    tss,
    start_pages,
    local_pages,
    block_pages: int = 8,
    interpret: bool = False,
):
    """Fused multi-shard multi-query filter+aggregate scan.

    Column planes are stacked per shard, (S, n_pages, page_size) int32
    with padding pages invisible (begin_ts = INT32_MAX); per-query
    operands are (n_queries,) int32; ``start_pages`` is the
    per-(shard, query) table of LOCAL stitch points, (S, n_queries)
    int32; ``local_pages`` (S,) int32 gives each shard's real
    (pre-padding) page count so whole padding blocks skip their DMA.
    Returns (sums, counts), each (n_queries,) int32 -- the partials
    accumulated over shards and blocks (int32 addition is associative,
    so the accumulation order cannot change the bits).
    """
    n_shards, n_pages, page_size = pred0.shape
    n_queries = los0.shape[0]

    planes, n_blocks = _pad_pages(
        (pred0, pred1, agg, begin_ts, end_ts), n_pages, block_pages, 1
    )
    pred0, pred1, agg, begin_ts, end_ts = planes

    qparams = jnp.stack(
        [jnp.asarray(v, jnp.int32) for v in (los0, his0, los1, his1, tss)]
    )
    start_pages = jnp.asarray(start_pages, jnp.int32)
    # Per-shard block window: [first block any query needs,
    # last block holding real pages].  The index map clamps the block
    # coordinate into this window, so prefix blocks AND trailing
    # padding blocks revisit a resident block (their DMAs are elided);
    # the kernel body adds nothing for them.
    first_blk = jnp.min(start_pages, axis=1) // block_pages
    last_blk = jnp.maximum(-(-local_pages // block_pages) - 1, 0)
    last_blk = jnp.minimum(last_blk, n_blocks - 1)
    first_blk = jnp.minimum(first_blk, last_blk)
    blocks = jnp.stack(
        [first_blk.astype(jnp.int32), last_blk.astype(jnp.int32)], axis=1
    )

    def _imap(s, i, q, qp, stt, bi):
        del qp, stt
        return (s, jnp.clip(i, bi[s, 0], bi[s, 1]), 0)

    block = pl.BlockSpec((1, block_pages, page_size), _imap)
    kernel = functools.partial(_sharded_kernel, block_pages=block_pages)
    sums, cnts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_shards, n_blocks, n_queries),
            in_specs=[block] * 5,
            out_specs=[_SMEM_OUT, _SMEM_OUT],
        ),
        out_shape=[_query_vector(n_queries), _query_vector(n_queries)],
        interpret=interpret,
    )(qparams, start_pages, blocks, pred0, pred1, agg, begin_ts, end_ts)
    return sums, cnts


# ---------------------------------------------------------------------------
# Masked (coverage-bitmap) variant: uncovered-only page selection
# ---------------------------------------------------------------------------
#
# The crack-on-scan table suffix.  Instead of a per-(shard, query)
# ``start_pages`` stitch point, the scalar-prefetch channel carries one
# per-shard row of PACKED COVERAGE WORDS (int32, little-endian bit
# order: bit ``p & 31`` of word ``p >> 5`` is local page p's built
# flag; ``core.index.PageCoverage.packed_words``).  Coverage is index
# state, not query state, so the operand is (S, W) -- shared by every
# query in the batch -- and the wrapper derives each shard's
# [first, last] *live* block window (blocks holding at least one
# uncovered real page) on the device before launch:
#
# * whole blocks outside the window skip pre-DMA exactly like the
#   prefix blocks of the start_pages kernel (the index map clamps into
#   the window, so skipped steps revisit a resident block);
# * inside the window, a vector bit test masks covered pages off: one
#   SMEM scalar load per coverage word the block spans (a static count,
#   since block_pages is a compile-time constant), shifted per row;
# * an all-covered shard encodes the empty window [1, 0]: no block
#   satisfies first <= blk <= last, and the index-map clamp still
#   lands in-bounds at block 0.
#
# Bit-exactness: a bitmap that is a prefix of length L yields the same
# page partition as start_pages = L, and the same visibility masking
# applies, so summed partials match the start_pages kernel bit for bit
# (int32 adds associate; tests/test_kernels.py pins this).


def _masked_sharded_kernel(
    qparams_ref,
    words_ref,
    blocks_ref,
    pred0_ref,
    pred1_ref,
    agg_ref,
    begin_ref,
    end_ref,
    sum_ref,
    cnt_ref,
    *,
    block_pages: int,
):
    """One grid step: reduce the UNCOVERED pages of one shard's
    (block_pages, page_size) tile for one query.

    Scalar-prefetch operands (SMEM):
      qparams_ref (5, n_queries) -- [lo0, hi0, lo1, hi1, ts] rows
      words_ref   (S, W)         -- packed little-endian coverage words
      blocks_ref  (S, 2)         -- per-shard [first_live_block,
                                    last_live_block] ([1, 0] = none)
    """
    s = pl.program_id(0)
    blk = pl.program_id(1)
    q = pl.program_id(2)
    lo0, hi0 = qparams_ref[0, q], qparams_ref[1, q]
    lo1, hi1 = qparams_ref[2, q], qparams_ref[3, q]
    ts = qparams_ref[4, q]

    first_page = blk * block_pages
    live = (blk >= blocks_ref[s, 0]) & (blk <= blocks_ref[s, 1])

    @pl.when((s == 0) & (blk == 0))
    def _init():
        sum_ref[q] = jnp.int32(0)
        cnt_ref[q] = jnp.int32(0)

    @pl.when(live)
    def _run():
        p0 = pred0_ref[...]
        p1 = pred1_ref[...]
        ag = agg_ref[...]
        bts = begin_ref[...]
        ets = end_ref[...]
        mask = (p0 >= lo0) & (p0 <= hi0) & (p1 >= lo1) & (p1 <= hi1)
        mask &= (bts <= ts) & (ts < ets)
        # Each page's built bit, as a vector: one SMEM word load per
        # coverage word the block spans, shifted by each row's bit
        # position (an arithmetic shift: the sign bit carries page 31
        # of a word, and ``>> 31 & 1`` still extracts it exactly).
        pages = first_page + jax.lax.broadcasted_iota(jnp.int32, p0.shape, 1)
        word_of = pages >> 5
        covered = jnp.zeros(p0.shape, jnp.int32)
        w0 = first_page >> 5
        n_words = words_ref.shape[1]
        for k in range(_words_spanned(block_pages)):
            w = w0 + k
            word = words_ref[s, jnp.minimum(w, n_words - 1)]
            hit = (word_of == w) & (w < n_words)
            covered = jnp.where(hit, (word >> (pages & 31)) & 1, covered)
        mask &= covered == 0
        sum_ref[q] += jnp.sum(jnp.where(mask, ag, 0), dtype=jnp.int32)
        cnt_ref[q] += jnp.sum(mask, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def sharded_batched_filter_agg_masked(
    pred0,
    pred1,
    agg,
    begin_ts,
    end_ts,
    los0,
    his0,
    los1,
    his1,
    tss,
    words,
    local_pages,
    block_pages: int = 8,
    interpret: bool = False,
):
    """Fused multi-shard multi-query scan of the UNCOVERED pages.

    Same plane / query-operand layout as ``sharded_batched_filter_agg``
    with the per-(shard, query) ``start_pages`` table replaced by the
    per-shard packed coverage words (S, W) int32.  Returns
    (sums, counts), each (n_queries,) int32 over uncovered pages only
    -- the caller adds the index half (``batched_masked_index_side``).
    A single-shard launch (S = 1) serves plain tables.
    """
    n_shards, n_pages, page_size = pred0.shape
    n_queries = los0.shape[0]

    planes, n_blocks = _pad_pages(
        (pred0, pred1, agg, begin_ts, end_ts), n_pages, block_pages, 1
    )
    pred0, pred1, agg, begin_ts, end_ts = planes

    qparams = jnp.stack(
        [jnp.asarray(v, jnp.int32) for v in (los0, his0, los1, his1, tss)]
    )
    words = jnp.asarray(words, jnp.int32)
    local_pages = jnp.asarray(local_pages, jnp.int32)

    # Per-shard live-block window from the unpacked bits: blocks
    # holding at least one uncovered REAL page.  All-covered shards
    # get the empty window [1, 0] (no block passes the kernel's range
    # test; the index-map clamp still lands in-bounds at block 0).
    W = words.shape[1]
    shifts = jnp.arange(32, dtype=jnp.int32)[None, None, :]
    bits = ((words[:, :, None] >> shifts) & 1).reshape(n_shards, W * 32)
    page_idx = jnp.arange(W * 32, dtype=jnp.int32)
    live_page = (bits == 0) & (page_idx[None, :] < local_pages[:, None])
    any_live = jnp.any(live_page, axis=1)
    first_pg = jnp.argmax(live_page, axis=1).astype(jnp.int32)
    last_pg = (W * 32 - 1 - jnp.argmax(live_page[:, ::-1], axis=1)).astype(
        jnp.int32
    )
    first_blk = jnp.where(any_live, first_pg // block_pages, 1)
    last_blk = jnp.where(
        any_live, jnp.minimum(last_pg // block_pages, n_blocks - 1), 0
    )
    blocks = jnp.stack(
        [first_blk.astype(jnp.int32), last_blk.astype(jnp.int32)], axis=1
    )

    def _imap(s, i, q, qp, wd, bi):
        del qp, wd
        return (s, jnp.clip(i, bi[s, 0], bi[s, 1]), 0)

    block = pl.BlockSpec((1, block_pages, page_size), _imap)
    kernel = functools.partial(
        _masked_sharded_kernel, block_pages=block_pages
    )
    sums, cnts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_shards, n_blocks, n_queries),
            in_specs=[block] * 5,
            out_specs=[_SMEM_OUT, _SMEM_OUT],
        ),
        out_shape=[_query_vector(n_queries), _query_vector(n_queries)],
        interpret=interpret,
    )(qparams, words, blocks, pred0, pred1, agg, begin_ts, end_ts)
    return sums, cnts
