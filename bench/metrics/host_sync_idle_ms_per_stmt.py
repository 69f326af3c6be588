"""Time the host spends reading device values while the device is
idle, per statement.

Layer: executor and planner (its host reads, and the tuner's and the
build's, ``repro.obs.host_read``).  Source: the device trace of the
window and the program's ``repro.sync`` spans in it
(``bench/program_spans.py``): the union of those spans less the part
in which some program ran on the device, over the statements the
window served.  A read that waits on a running program costs nothing
here; one that holds the device idle (a transfer, or the host work
around it) does.  Moves ``stmts_per_s``.
"""

from bench import program_spans as ps
from bench import trace as tr


def read(ctx):
    if not ctx.device_runs():
        return None
    syncs = tr.union(ps.in_window(ctx, (ps.SYNC,)))
    served = len(ctx.window_served())
    if not syncs or not served:
        return None
    idle = tr.length(syncs) - tr.overlap(syncs, ctx.busy)
    return 1e3 * idle / served
