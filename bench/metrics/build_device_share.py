"""Share of the window the device spends in index-build programs.

Layer: tuner and build lane (``core/tuner.py``, ``core/build_service.py``
and ``core/index.py``'s VAP build).  Source: the device trace of the
window; a build program is one whose name, as the trace prints it
today, is in ``PROGRAMS``.  0 where the window ran none.  Moves
``stmts_per_s``: a cycle's build quanta run before the next call, which
waits for them.
"""

PROGRAMS = (
    "_build_pages_vap",
    "build_pages_at",
    "sharded_build_pages_vap",
    "vbp_populate_subdomain",
    "sharded_vbp_populate_subdomain",
)


def read(ctx):
    if not ctx.device_runs():
        return None
    runs = [r for r in ctx.device_runs() if r[0] in PROGRAMS]
    seconds = sum(e - s for _, s, e in runs) / ctx.n_devices
    return 100.0 * seconds / ctx.window_s
