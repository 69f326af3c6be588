"""Device time of the write programs per UPDATE the window served.

Layer: MVCC write path (``core/table.py``'s ``update_rows`` and
``insert_rows``, and ``copy_table``, the one copy a database makes of a
table it does not own before it writes; their dispatch in
``Database._exec_update`` / ``_exec_insert``, ``core/executor.py``).
Source: the device trace of the window; a write program is one whose
name, as the trace prints it today, is in ``PROGRAMS``.  The statements
are the window's UPDATEs.  Moves ``stmts_per_s``: the statements of a
burst behind an UPDATE wait for it.
"""

PROGRAMS = ("update_rows", "insert_rows", "copy_table")


def read(ctx):
    runs = [r for r in ctx.device_runs() if r[0] in PROGRAMS]
    updates = sum(1 for s in ctx.window_served()
                  if s.query.kind == "update")
    if not runs or not updates:
        return None
    seconds = sum(e - s for _, s, e in runs) / ctx.n_devices
    return 1e3 * seconds / updates
