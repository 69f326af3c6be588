"""The program's own spans in a traced window.

``src/repro`` wraps its layer boundaries in ``jax.profiler`` spans
named ``repro.<layer>`` (``repro/obs.py``).  They run on the thread
that holds the benchmark's ``bench.`` spans, so ``bench/trace.py``
keeps them in ``Trace.host``, on the clock of the device planes.  A
program without them (one older than its spans) leaves every reader
of this module with nothing to read.
"""

ROOTS = ("repro.execute_batch", "repro.execute")
DISPATCH = "repro.executor.dispatch"
SYNC = "repro.sync"
DECIDE = "repro.tuner.decide"
APPLY = "repro.build.apply"
# Spans inside a statement call that are not the executor's own time.
CHILDREN = (DISPATCH, SYNC, APPLY, DECIDE)


def in_window(ctx, names):
    """(start, end) of every span named in ``names``, clipped to the
    window; spans outside it are left out."""
    w0, w1 = ctx.window
    return sorted(
        (max(s, w0), min(e, w1))
        for name in names
        for s, e in ctx.trace.spans(name)
        if e > w0 and s < w1
    )
