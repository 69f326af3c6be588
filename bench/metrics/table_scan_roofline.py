"""Table-scan programs' share of the HBM roofline.

Layer: scan kernels (``kernels/`` and the table-scan forms of
``core/hybrid_scan.py`` and ``core/engine.py``, jnp or Pallas alike).
Source: the device trace of the window.

A table scan compares a few int32 columns with constants and adds
one: about one operation per byte read, against the chip's hundreds of
operations per byte of bandwidth.  It is bound by HBM bandwidth, so
its least time is the least bytes over the peak bandwidth of the
``device_kind`` (``bench/peaks.py``).  The least bytes of one dispatch
of a table-plan group are each plane it must read counted once (the
predicate columns, the aggregate column, ``begin_ts`` and ``end_ts``)
over the pages that hold rows, ``ceil(n_rows / page_size)`` of them,
not the headroom.  The groups come from the window's statements: the
scans of one burst that no index served, by (predicate attributes,
aggregate attribute).  ``n_rows`` is the table's rows as loaded; the
cell that reads this is read-only.  A table-scan program is one whose
name, as the trace prints it today, is in ``PROGRAMS``.

share = least bytes / (device time of those programs * peak), in %.
Moves ``stmts_per_s``.
"""

import math

PROGRAMS = (
    "batched_full_table_scan",
    "full_table_scan",
    "sharded_full_table_scan",
    "_stacked_batched_full",
    "sharded_batched_full_table_scan_loop",
)


def least_bytes(ctx):
    g = ctx.geometry
    pages = math.ceil(g["n_rows"] / g["page_size"])
    per_plane = pages * g["page_size"] * 4
    total = 0
    for groups in ctx.window_table_groups():
        for attrs, agg in groups:
            total += (len(set(attrs) | {agg}) + 2) * per_plane
    return total


def read(ctx):
    runs = [r for r in ctx.device_runs() if r[0] in PROGRAMS]
    seconds = sum(e - s for _, s, e in runs) / ctx.n_devices
    need = least_bytes(ctx)
    if seconds <= 0 or need <= 0:
        return None
    return 100.0 * need / (seconds * ctx.peaks["hbm_bytes_per_s"])
