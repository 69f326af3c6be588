"""Copies of a whole table made inside the window.

Layer: MVCC write path (``Database._writable``, ``core/executor.py``).
A database writes in place only to a table its own last write
returned; it copies any other table once (``core/table.py``'s
``copy_table``) inside a ``repro.table.own`` span before its first
write to it.  The cell's first write falls in the untimed warm-up
statements, so this reads 0; a copy in the window moves the whole table
through HBM twice while a statement waits.  Source: the program's spans
in the window (``bench/program_spans.py``).  A program without that
copy (one older than it), or a trace with no program spans at all,
leaves nothing to read.  Moves ``stmts_per_s``.
"""

from bench import program_spans as ps

OWN = "repro.table.own"


def _program_copies_once() -> bool:
    try:
        from repro.core import table
    except ImportError:
        return False
    return hasattr(table, "copy_table")


def read(ctx):
    if not any(n.startswith("repro.") for n, _, _ in ctx.trace.host):
        return None
    if not _program_copies_once():
        return None
    return float(len(ps.in_window(ctx, (OWN,))))
