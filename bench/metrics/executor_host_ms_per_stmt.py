"""Host time of the statement calls while the device is idle, per
statement.

Layer: executor and planner (``core/executor.py``, ``core/planner.py``).
Source: the device trace of the window and the benchmark's spans
around ``Database.execute_batch`` and ``Database.execute`` in it: each
span's length less the part in which some program ran on the device,
summed and divided by the statements the window served.  Moves
``stmts_per_s``.
"""

from bench import trace as tr
from bench.driver import STATEMENT_SPANS


def read(ctx):
    if not ctx.device_runs():
        return None
    spans = tr.union(
        (max(s, ctx.window[0]), min(e, ctx.window[1]))
        for name in STATEMENT_SPANS
        for s, e in ctx.trace.spans(name)
        if e > ctx.window[0] and s < ctx.window[1]
    )
    served = len(ctx.window_served())
    if not spans or not served:
        return None
    idle = tr.length(spans) - tr.overlap(spans, ctx.busy)
    return 1e3 * idle / served
