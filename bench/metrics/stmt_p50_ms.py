"""Median statement latency in the traced window, from due time to
result on the host, over every statement the window served.

Layer: statement API (``repro.api.Database.execute_batch`` and
``execute``, behind the open loop's queue).  Source: the host clock.
Above the knee the queue grows through the window, so this follows
the rate the system sustains: it moves ``stmts_per_s``.
"""

import numpy as np


def read(ctx):
    lat = [1e3 * (s.done - s.due) for s in ctx.window_served()]
    return float(np.percentile(lat, 50)) if lat else None
