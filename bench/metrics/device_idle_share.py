"""Share of the window in which no program runs on the device.

Layer: device.  Source: the device trace of the window: 1 minus the
union of the program runs' intervals over the window's length, in %,
averaged over the chips.  Moves ``stmts_per_s``.
"""


def read(ctx):
    if not ctx.device_runs():
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
