"""Device time of index-plan scan programs per scan served by an index.

Layer: index and hybrid scan (``core/hybrid_scan.py``, the index scans
of ``core/index.py``, their dispatch in ``core/engine.py``).  Source:
the device trace of the window.  An index-plan program is one whose
name, as the trace prints it today, is in ``PROGRAMS``: the dispatch
programs of the ``hybrid``, ``hybrid_ps``, ``hybrid_masked`` and
``pure_*`` plans.  The statements are the window's scans that the
program reports as served through an index.  Moves ``stmts_per_s``.
"""

PROGRAMS = (
    "batched_hybrid_scan",
    "batched_hybrid_scan_masked",
    "batched_pure_index_scan",
    "batched_hybrid_index_prefix",
    "batched_masked_index_side",
    "hybrid_scan",
    "hybrid_scan_masked",
    "pure_index_scan",
    "_stacked_batched_hybrid",
    "_stacked_batched_hybrid_ps",
    "_stacked_batched_pure_index",
    "_stacked_batched_masked",
    "_stacked_hybrid_prefix",
    "_stacked_hybrid_prefix_ps",
)


def read(ctx):
    runs = [r for r in ctx.device_runs() if r[0] in PROGRAMS]
    served = sum(1 for s in ctx.window_served()
                 if s.query.kind == "scan" and s.used_index)
    if not runs or not served:
        return None
    seconds = sum(e - s for _, s, e in runs) / ctx.n_devices
    return 1e3 * seconds / served
