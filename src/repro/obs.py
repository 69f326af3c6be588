"""Spans and host reads on the JAX profiler's clock.

Every span of the program is a ``jax.profiler.TraceAnnotation``: the
profiler writes it into its host plane, on the clock of the device
planes, keeps it in memory and writes it out at ``stop_trace``.  With
no trace running a span only builds the annotation object.

Span names start with ``repro.``.  Keyword arguments are counts the
code already holds on the host (a page count, a name, a length); one
known only when the work ends goes on with ``set_metadata`` before the
span closes::

    with span("repro.build.apply", index=name, pages=n) as sp:
        ...
        sp.set_metadata(pages_built=done)

The profiler records no argument whose value is an empty string.

A device value is never read only to put it on a span: that would add
a host sync.  Every read of a device value on the served path goes
through ``host_read``, so each shows in the trace as a ``repro.sync``
span whose ``site`` names the caller.
"""

from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation


def span(name: str, **counts) -> TraceAnnotation:
    """A profiler span named ``name`` carrying ``counts``."""
    return TraceAnnotation(name, **counts)


def host_read(x, site: str):
    """``jax.device_get(x)`` inside a ``repro.sync`` span; a host value
    passes through unchanged."""
    with TraceAnnotation("repro.sync", site=site):
        return jax.device_get(x)
