"""Pallas TPU kernel: fused predicate-filter + aggregate table scan.

This is the compute hot-spot of the paper's workload: every scan query
(LOW-S / MOD-S / HIGH-S) bottoms out in "evaluate a conjunctive range
predicate over a table region and aggregate the matches".  The paper
optimises this path on CPU via its columnar layout + hybrid scan; the
TPU-native adaptation re-blocks it for the memory hierarchy:

* Columns arrive as separate (n_pages, page_size) int32 planes (the
  layout tuner's grouping already stores hot attributes contiguously),
  so each grid step streams ``block_pages`` pages of exactly the
  predicate/aggregate columns HBM -> VMEM -- never the full row width.
* ``page_size`` is the lane dimension (multiples of 128); block_pages
  the sublane dimension (multiples of 8 for int32 tiling), so the
  predicate evaluates on full VPU vregs.
* The hybrid-scan variant receives ``start_page`` as a scalar-prefetch
  operand (SMEM): grid steps whose page block lies entirely inside the
  already-indexed prefix skip their work (``pl.when``) -- the TPU
  analogue of the operator starting its table scan at
  max(rho_m, rho_i + 1).  Scalar prefetch means the skip is decided
  before the DMA is issued, so skipped blocks cost neither bandwidth
  nor compute.
* (sum, count) accumulate across grid steps in two whole-array SMEM
  scalars (a per-step scalar output block would break the (8, 128)
  tiling rule); accumulation stays int32 (the engine's documented
  wraparound semantics).

MVCC visibility (begin_ts <= ts < end_ts) is fused into the predicate,
so the kernel implements the full semantics of the engine's visible
scan, not a simplification.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MIN = -(2**31)
I32_MAX = 2**31 - 1


def _filter_agg_kernel(
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
    use_start_page: bool,
):
    """One grid step: reduce a (block_pages, page_size) tile.

    scalars_ref (SMEM, scalar-prefetch):
    [lo0, hi0, lo1, hi1, ts, start_page]
    """
    pid = pl.program_id(0)
    lo0, hi0 = scalars_ref[0], scalars_ref[1]
    lo1, hi1 = scalars_ref[2], scalars_ref[3]
    ts = scalars_ref[4]
    start_page = scalars_ref[5]

    first_page = pid * block_pages

    @pl.when(pid == 0)
    def _init():
        sum_ref[0] = jnp.int32(0)
        cnt_ref[0] = jnp.int32(0)

    def body():
        p0 = pred0_ref[...]
        p1 = pred1_ref[...]
        ag = agg_ref[...]
        bts = begin_ref[...]
        ets = end_ref[...]
        mask = (p0 >= lo0) & (p0 <= hi0) & (p1 >= lo1) & (p1 <= hi1)
        mask &= (bts <= ts) & (ts < ets)
        if use_start_page:
            # Per-page mask inside a block that straddles start_page.
            rows = jax.lax.broadcasted_iota(jnp.int32, p0.shape, 0)
            mask &= (first_page + rows) >= start_page
        sum_ref[0] += jnp.sum(jnp.where(mask, ag, 0), dtype=jnp.int32)
        cnt_ref[0] += jnp.sum(mask, dtype=jnp.int32)

    if use_start_page:
        # Blocks entirely inside the indexed prefix add nothing.
        pl.when(first_page + block_pages > start_page)(body)
    else:
        body()


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def filter_agg(
    pred0,
    pred1,
    agg,
    begin_ts,
    end_ts,
    lo0,
    hi0,
    lo1,
    hi1,
    ts,
    start_page=None,
    block_pages: int = 8,
    interpret: bool = False,
):
    """Fused filter+aggregate scan.  See ref.filter_agg_ref for the
    contract; ``start_page`` switches on the hybrid-scan page skip
    (ref.masked_filter_agg_ref).

    All column planes are (n_pages, page_size) int32.  ``page_size``
    should be a multiple of 128 and ``block_pages`` a multiple of 8
    for native int32 tiling (the wrapper pads the page axis).
    """
    n_pages, page_size = pred0.shape
    use_start = start_page is not None
    if not use_start:
        start_page = 0

    grid = pl.cdiv(n_pages, block_pages)
    pad = grid * block_pages - n_pages
    if pad:
        # Padding rows carry begin_ts = INT32_MAX -> never visible.
        def padp(x, fill):
            return jnp.pad(x, ((0, pad), (0, 0)), constant_values=fill)

        pred0 = padp(pred0, 0)
        pred1 = padp(pred1, 0)
        agg = padp(agg, 0)
        begin_ts = padp(begin_ts, I32_MAX)
        end_ts = padp(end_ts, I32_MAX)

    scalars = jnp.stack(
        [
            jnp.asarray(v, jnp.int32)
            for v in (lo0, hi0, lo1, hi1, ts, start_page)
        ]
    )

    # index_map receives (*grid_indices, *scalar_prefetch_refs).  The
    # hybrid variant clamps the block coordinate up to the first block
    # the scan needs: the skipped prefix revisits that resident block,
    # so its DMAs are elided (the pre-DMA skip); pl.when keeps the
    # prefix out of the sums.
    if use_start:

        def _imap(i, s):
            first = jnp.minimum(s[5] // block_pages, grid - 1)
            return (jnp.maximum(i, first), 0)

        block = pl.BlockSpec((block_pages, page_size), _imap)
    else:
        block = pl.BlockSpec((block_pages, page_size), lambda i, s: (i, 0))
    out_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _filter_agg_kernel,
        block_pages=block_pages,
        use_start_page=use_start,
    )
    sums, cnts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[block] * 5,
            out_specs=[out_spec, out_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret,
    )(scalars, pred0, pred1, agg, begin_ts, end_ts)
    return sums[0], cnts[0]
