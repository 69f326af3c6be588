"""Statements completed per second of the window.  The window issues
calls for ``--seconds`` and then waits for the last one; the rate is
every statement it served over the time from its start to that last
result.  Host clock."""


def read(run):
    served = len(run.record.served)
    return served / run.record.window_s if served else None
