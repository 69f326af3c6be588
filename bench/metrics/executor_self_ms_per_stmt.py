"""The executor's own host time while the device is idle, per
statement.

Layer: executor and planner (``core/executor.py``, ``core/planner.py``).
Source: the device trace of the window and the program's spans in it
(``bench/program_spans.py``): the time inside the statement calls'
root spans (``repro.execute_batch``, ``repro.execute``) that lies in
none of their child spans (group dispatches, host reads, and any
tuning decide or build quantum under them) and in no program run on
the device, over the statements the window served.  The twin, from
inside the program, of ``executor_host_ms_per_stmt``, with dispatch
and host reads taken out.  Moves ``stmts_per_s``.
"""

from bench import program_spans as ps
from bench import trace as tr


def read(ctx):
    if not ctx.device_runs():
        return None
    roots = tr.union(ps.in_window(ctx, ps.ROOTS))
    served = len(ctx.window_served())
    if not roots or not served:
        return None
    held = tr.union(ps.in_window(ctx, ps.CHILDREN) + list(ctx.busy))
    free = tr.gaps(held, ctx.window)
    return 1e3 * tr.overlap(roots, free) / served
