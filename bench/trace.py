"""Reduce a JAX profiler trace to device programs and host spans.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` file.  On a TPU
each chip is a plane named ``/device:TPU:<n>`` whose line ``XLA
Modules`` holds one event per program run, named
``jit_<function>(<fingerprint>)``.  On the CPU backend there is no
device plane: the operations of a program run on host threads, and
each carries the stats ``hlo_module`` and ``run_id``, so one program
run is the span of the operations that share them.  Host spans are the
events of the thread that holds the benchmark's own spans (named
``bench.<call>``); the name of that thread's line differs between
backends.

All times are seconds on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

_FINGERPRINT = re.compile(r"\(\d+\)$")


def program_name(raw: str) -> str:
    """``jit_batched_hybrid_scan(123)`` -> ``batched_hybrid_scan``."""
    name = _FINGERPRINT.sub("", raw)
    return name[4:] if name.startswith("jit_") else name


@dataclass
class Trace:
    # (program, start, end) per program run, per device
    programs: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    # (name, start, end) of the Python thread's events
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    def spans(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.host if n == name]

    def window(self, name: str) -> Optional[Interval]:
        got = self.spans(name)
        return got[0] if got else None

    def device_runs(self, window: Optional[Interval] = None):
        """Program runs of every device, clipped to ``window``."""
        out = []
        for runs in self.programs.values():
            for name, s, e in runs:
                if window is not None:
                    s, e = max(s, window[0]), min(e, window[1])
                    if e <= s:
                        continue
                out.append((name, s, e))
        return out


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    cpu_runs: Dict[tuple, List[float]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith(
            "/device:CUSTOM"
        ):
            runs = []
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    runs.append((program_name(ev.name), s,
                                 s + ev.duration_ns * 1e-9))
            if runs:
                tr.programs[plane.name] = runs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = list(line.events)
                if any(ev.name.startswith("bench.") for ev in events):
                    tr.host.extend(
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in events)
                for ev in events:
                    st = _stats(ev)
                    if "hlo_module" not in st or "run_id" not in st:
                        continue
                    key = (str(st["hlo_module"]), int(st["run_id"]))
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    got = cpu_runs.setdefault(key, [s, e])
                    got[0], got[1] = min(got[0], s), max(got[1], e)
    if not tr.programs and cpu_runs:
        tr.programs["/host:CPU"] = sorted(
            (program_name(m), s, e) for (m, _), (s, e) in cpu_runs.items())
    tr.host.sort(key=lambda x: x[1])
    return tr


def start(trace_dir: str) -> None:
    """Start the profiler with host spans on and the Python call tracer
    and HLO protos off: the readers need neither, and both cost time in
    the window and bytes on disk."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find(trace_dir: str) -> str:
    got = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not got:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return got[-1]


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """Idle intervals of ``window`` outside the sorted ``busy`` union."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return [(s, e) for s, e in out if e > s]


def innermost(host, t: float) -> Optional[str]:
    """Name of the shortest host event covering time ``t``."""
    best = None
    for name, s, e in host:
        if s > t:
            break
        if e >= t:
            if best is None or e - s < best[1]:
                best = (name, e - s)
    return best[0] if best else None
