"""UPDATE programs' share of the HBM roofline.

Layer: MVCC write path (``core/table.py``'s ``update_rows``, dispatched
by ``Database._exec_update`` in ``core/executor.py``).  Source: the
device trace of the window.

An UPDATE compares a few int32 columns with constants over the whole
table, picks the first ``update_cap`` matches and writes their new
versions: a handful of operations per byte, so it is bound by HBM
bandwidth, and its least time is its least bytes over the peak
bandwidth of the ``device_kind`` (``bench/peaks.py``).  The least bytes
of one UPDATE are:

* each plane it must read counted once (its predicate columns,
  ``begin_ts`` and ``end_ts``) over the pages that hold rows,
  ``ceil(n_rows / page_size)`` of them, not the headroom;
* the new versions it writes, ``rows_modified * (n_cols + 2) * 4`` B;
* their old versions' terminations, ``rows_modified * 4`` B.

``n_rows`` is the table's rows as loaded plus what the window's earlier
UPDATEs appended (their ``rows_modified``), at most the capacity.  An
UPDATE program is one whose name, as the trace prints it today, is in
``PROGRAMS``.

share = least bytes / (device time of those programs * peak), in %.
Moves ``stmts_per_s``.
"""

import math

PROGRAMS = ("update_rows",)


def least_bytes(ctx):
    g = ctx.geometry
    psz, n_rows = g["page_size"], g["n_rows"]
    total = 0
    for s in ctx.window_served():
        q = s.query
        if q.kind != "update":
            continue
        pages = math.ceil(n_rows / psz)
        total += (len(set(q.attrs)) + 2) * pages * psz * 4
        modified = int(s.result[2])
        total += modified * (g["n_cols"] + 2) * 4 + modified * 4
        n_rows = min(n_rows + modified, g["capacity"])
    return total


def read(ctx):
    runs = [r for r in ctx.device_runs() if r[0] in PROGRAMS]
    seconds = sum(e - s for _, s, e in runs) / ctx.n_devices
    if seconds <= 0:
        return None
    need = least_bytes(ctx)
    if need <= 0:
        return None
    return 100.0 * need / (seconds * ctx.peaks["hbm_bytes_per_s"])
