"""Jit'd public wrappers around the Pallas scan kernels.

``scan_table_batched`` adapts the engine's Table layout (columns
stacked in one (n_pages, page_size, n_attrs) array) to the multi-query
kernel's column-plane interface and picks hardware-aligned block
shapes; a single query is a batch of one.  ``scan_shards_batched`` is
the fused multi-shard form over a stacked shard pytree
(``core.table.stacked_shards``); the ``*_masked`` forms scan the
pages a coverage bitmap leaves uncovered.

Every wrapper's ``interpret=None`` resolves through ``interpret_mode``:
the Pallas interpreter runs exactly when JAX's default backend is the
CPU (the test suite), and a TPU backend always gets the compiled
kernel -- never the interpreter on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import batched_filter_agg as _bfa

I32_MIN = _bfa.I32_MIN
I32_MAX = _bfa.I32_MAX

def interpret_mode() -> bool:
    """Run the Pallas kernels in interpret mode?  True only when JAX's
    default backend is the CPU, which has no Pallas TPU lowering."""
    return jax.default_backend() == "cpu"


def _pick_block_pages(n_pages: int) -> int:
    for bp in (64, 32, 16, 8):
        if n_pages >= bp:
            return bp
    return 8


def _block_pages(n_pages, page_size, interpret, n_planes=5):
    """Resolve the page-axis block size for a launch.

    Interpret mode (the CPU container) keeps the historical fixed
    ladder so results and timings stay bit-for-bit reproducible; a
    real-hardware launch sizes blocks to the chip's VMEM via
    ``batched_filter_agg.tpu_block_pages`` (5 int32 planes stream per
    grid step: pred0, pred1, agg, begin_ts, end_ts).
    """
    if interpret:
        return _pick_block_pages(n_pages)
    return _bfa.tpu_block_pages(n_pages, page_size, n_planes=n_planes)


def _batch_bounds(data, attrs, los, his):
    """Split per-query (B, len(attrs)) bounds into the kernels' two
    predicate-plane/bounds pairs (1-attr queries widen the second)."""
    los = jnp.asarray(los, jnp.int32)
    his = jnp.asarray(his, jnp.int32)
    n_queries = los.shape[0]
    pred0 = data[..., attrs[0]]
    los0, his0 = los[:, 0], his[:, 0]
    if len(attrs) == 2:
        pred1 = data[..., attrs[1]]
        los1, his1 = los[:, 1], his[:, 1]
    else:
        pred1 = pred0
        los1 = jnp.full((n_queries,), I32_MIN, jnp.int32)
        his1 = jnp.full((n_queries,), I32_MAX, jnp.int32)
    return pred0, pred1, los0, his0, los1, his1


def scan_table_batched(
    table, attrs, los, his, tss, agg_attr, start_pages=None, interpret=None
):
    """Batched multi-query filter+aggregate via the Pallas kernel.

    All queries share the table, the constrained ``attrs`` (1 or 2
    columns) and ``agg_attr``; ``los``/``his`` are (n_queries,
    len(attrs)) per-query inclusive bounds, ``tss`` (n_queries,)
    snapshot timestamps, ``start_pages`` (n_queries,) hybrid-scan
    stitch points (None = full scans).  Returns (sums, counts), each
    (n_queries,) int32.
    """
    if len(attrs) not in (1, 2):
        raise ValueError(
            f"kernel scans support 1 or 2 predicate attributes, "
            f"got {attrs!r}"
        )
    interpret = interpret_mode() if interpret is None else interpret
    n_queries = jnp.asarray(los).shape[0]
    pred0, pred1, los0, his0, los1, his1 = _batch_bounds(
        table.data, attrs, los, his
    )
    if start_pages is None:
        start_pages = jnp.zeros((n_queries,), jnp.int32)
    agg = table.data[..., agg_attr]
    return _bfa.batched_filter_agg(
        pred0,
        pred1,
        agg,
        table.begin_ts,
        table.end_ts,
        los0,
        his0,
        los1,
        his1,
        jnp.asarray(tss, jnp.int32),
        jnp.asarray(start_pages, jnp.int32),
        block_pages=_block_pages(table.n_pages, table.page_size, interpret),
        interpret=interpret,
    )


def scan_shards_batched(
    stacked, attrs, los, his, tss, agg_attr, start_pages, interpret=None
):
    """Fused multi-shard multi-query scan via the Pallas kernel.

    ``stacked`` is a ``core.table.StackedShards`` (cached padded
    shard pytree); queries share the constrained ``attrs`` (1 or 2
    columns) and ``agg_attr``; ``los``/``his`` are (n_queries,
    len(attrs)) per-query inclusive bounds, ``tss`` (n_queries,)
    snapshot timestamps and ``start_pages`` the (n_shards, n_queries)
    table of per-shard LOCAL stitch points (zeros = full scans).
    Returns (sums, counts), each (n_queries,) int32, already reduced
    over the shard axis.
    """
    if len(attrs) not in (1, 2):
        raise ValueError(
            f"kernel scans support 1 or 2 predicate attributes, "
            f"got {attrs!r}"
        )
    interpret = interpret_mode() if interpret is None else interpret
    t = stacked.table
    pred0, pred1, los0, his0, los1, his1 = _batch_bounds(
        t.data, attrs, los, his
    )
    agg = t.data[..., agg_attr]
    return _bfa.sharded_batched_filter_agg(
        pred0,
        pred1,
        agg,
        t.begin_ts,
        t.end_ts,
        los0,
        his0,
        los1,
        his1,
        jnp.asarray(tss, jnp.int32),
        jnp.asarray(start_pages, jnp.int32),
        jnp.asarray(stacked.local_pages, jnp.int32),
        block_pages=_block_pages(t.data.shape[1], t.data.shape[2], interpret),
        interpret=interpret,
    )


def scan_table_batched_masked(
    table, attrs, los, his, tss, agg_attr, words, interpret=None
):
    """Masked-stitch table suffix over a plain Table: scans exactly
    the UNCOVERED pages of the coverage bitmap, whose packed words
    (1, W) int32 ride the scalar-prefetch channel
    (``PageCoverage.packed_words``).  Returns (sums, counts), each
    (n_queries,) int32 -- the caller adds the covered-page index half
    (``hybrid_scan.batched_masked_index_side``).  Runs as a one-shard
    launch of the sharded masked kernel."""
    if len(attrs) not in (1, 2):
        raise ValueError(
            f"kernel scans support 1 or 2 predicate attributes, "
            f"got {attrs!r}"
        )
    interpret = interpret_mode() if interpret is None else interpret
    pred0, pred1, los0, his0, los1, his1 = _batch_bounds(
        table.data, attrs, los, his
    )
    agg = table.data[..., agg_attr]
    return _bfa.sharded_batched_filter_agg_masked(
        pred0[None],
        pred1[None],
        agg[None],
        table.begin_ts[None],
        table.end_ts[None],
        los0,
        his0,
        los1,
        his1,
        jnp.asarray(tss, jnp.int32),
        jnp.asarray(words, jnp.int32),
        jnp.asarray([table.n_pages], jnp.int32),
        block_pages=_block_pages(table.n_pages, table.page_size, interpret),
        interpret=interpret,
    )


def scan_shards_batched_masked(
    stacked, attrs, los, his, tss, agg_attr, words, interpret=None
):
    """Fused multi-shard masked-stitch table suffix: ONE launch scans
    every shard's uncovered pages, selected pre-DMA from the per-shard
    packed coverage words (S, W) int32.  Same plane layout and query
    operands as ``scan_shards_batched`` with the ``start_pages`` table
    replaced by the coverage words."""
    if len(attrs) not in (1, 2):
        raise ValueError(
            f"kernel scans support 1 or 2 predicate attributes, "
            f"got {attrs!r}"
        )
    interpret = interpret_mode() if interpret is None else interpret
    t = stacked.table
    pred0, pred1, los0, his0, los1, his1 = _batch_bounds(
        t.data, attrs, los, his
    )
    agg = t.data[..., agg_attr]
    return _bfa.sharded_batched_filter_agg_masked(
        pred0,
        pred1,
        agg,
        t.begin_ts,
        t.end_ts,
        los0,
        his0,
        los1,
        his1,
        jnp.asarray(tss, jnp.int32),
        jnp.asarray(words, jnp.int32),
        jnp.asarray(stacked.local_pages, jnp.int32),
        block_pages=_block_pages(t.data.shape[1], t.data.shape[2], interpret),
        interpret=interpret,
    )
