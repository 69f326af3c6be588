"""The loop that drives the system's statement API in the window.

A copy of the closed loop of ``repro.bench_db.runner`` (burst staging,
tuning cycles fired on the database's own clock before each burst or
statement), with wall-clock arrivals added:

* ``open``: statements are due at fixed times from the window's start.
  When the head of the queue is due, a burst takes it and the scans
  behind it that are already due, up to ``burst_max``, and never past
  an update or a phase change.  An update runs alone.  When nothing is
  due the loop waits.  A statement's latency runs from its due time
  to its result on the host.
* ``closed``: ``clients`` statements are issued together as one burst
  and the next burst when the last one completes.

Both loops issue calls until the window's time is up and then wait
for the last call to complete; the window's length runs to that last
result, so a rate over it counts all the work and all its time.
Statements still queued at the close are not issued.

Scans go through ``Database.execute_batch``, updates through
``Database.execute``, and the tuner through ``PredictiveTuner.on_query``
and ``tuning_cycle``.  No tier or kernel option is passed: the program
chooses its own path.  Every call into the system is wrapped in a
``jax.profiler.TraceAnnotation`` span named ``bench.<call>``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax

SPAN_BATCH = "bench.execute_batch"
SPAN_EXECUTE = "bench.execute"
SPAN_CYCLE = "bench.tuning_cycle"
SPAN_WAIT = "bench.wait_arrival"
SPAN_WINDOW = "bench.window"
STATEMENT_SPANS = (SPAN_BATCH, SPAN_EXECUTE)


@dataclass
class Served:
    """One statement as the window served it."""

    query: object
    due: float  # s from the window's start (closed loop: issue time)
    done: float  # s from the window's start
    result: tuple  # (agg_sum, count, rows_modified)
    used_index: bool
    sim_latency_ms: float


@dataclass
class Record:
    served: List[Served] = field(default_factory=list)
    # Per call into the system, its table-plan scan groups: (predicate
    # attributes, aggregate attribute) of the scans no index served.
    table_groups: List[List[tuple]] = field(default_factory=list)
    window_s: float = 0.0  # from the window's start to its last result


class Driver:
    """Fires tuning cycles on the database clock and serves statements."""

    def __init__(self, db, tuner, interval_ms: Optional[float],
                 max_cycles_per_gap: int = 50):
        self.db = db
        self.tuner = tuner
        self.interval_ms = interval_ms
        self.max_cycles = max_cycles_per_gap
        self.next_cycle_ms = (
            db.clock_ms + interval_ms if interval_ms else float("inf")
        )
        self.cycles = 0

    # -- tuning cycles (runner._run_closed_loop.run_due_cycles) -----------
    def run_due_cycles(self) -> None:
        if not self.interval_ms:
            return
        db = self.db
        fired = 0
        while db.clock_ms >= self.next_cycle_ms and fired < self.max_cycles:
            with jax.profiler.TraceAnnotation(SPAN_CYCLE):
                work = self.tuner.tuning_cycle(idle=False)
            db.clock_ms += max(work * db.time_per_unit_ms, 1e-9)
            self.next_cycle_ms += self.interval_ms
            fired += 1
            self.cycles += 1
        if db.clock_ms >= self.next_cycle_ms:  # drop missed slots
            missed = (db.clock_ms - self.next_cycle_ms) // self.interval_ms
            self.next_cycle_ms += (int(missed) + 1) * self.interval_ms

    def _account(self, q, stats) -> None:
        extra = self.tuner.on_query(q, stats)
        self.db.clock_ms += extra * self.db.time_per_unit_ms

    # -- one call into the system ------------------------------------------
    def serve(self, stmts, rec: Record, dues, t0: float) -> float:
        """Serve ``stmts`` (one update, or a burst of scans) as one call;
        returns the completion time in s from ``t0``."""
        self.run_due_cycles()
        queries = [s.query for s in stmts]
        if len(stmts) == 1 and queries[0].kind != "scan":
            with jax.profiler.TraceAnnotation(SPAN_EXECUTE):
                stats_list = [self.db.execute(queries[0])]
        else:
            with jax.profiler.TraceAnnotation(SPAN_BATCH):
                stats_list = self.db.execute_batch(queries)
        done = time.perf_counter() - t0
        groups = []
        for s, due, st in zip(stmts, dues, stats_list):
            self._account(s.query, st)
            q = s.query
            if q.kind == "scan" and not st.used_index:
                g = (tuple(q.attrs), int(q.agg_attr))
                if g not in groups:
                    groups.append(g)
            rec.served.append(Served(
                query=q,
                due=due,
                done=done,
                result=(int(st.agg_sum), int(st.count),
                        int(st.rows_modified)),
                used_index=bool(st.used_index),
                sim_latency_ms=float(st.latency_ms),
            ))
        rec.table_groups.append(groups)
        return done

    # -- loops ---------------------------------------------------------------
    def bursts(self, stmts, burst_max: int):
        """Split statements, in order, as the closed loop stages them:
        scans in bursts of up to ``burst_max`` within one phase, each
        update alone."""
        out, cur = [], []
        for s in stmts:
            if s.query.kind != "scan":
                if cur:
                    out.append(cur)
                    cur = []
                out.append([s])
                continue
            if cur and (len(cur) >= burst_max or cur[0].phase != s.phase):
                out.append(cur)
                cur = []
            cur.append(s)
        if cur:
            out.append(cur)
        return out

    def warm(self, stmts, burst_max: int, rec: Record) -> None:
        """Serve statements back to back, untimed (the set-up's share)."""
        t0 = time.perf_counter()
        for burst in self.bursts(stmts, burst_max):
            self.serve(burst, rec, [0.0] * len(burst), t0)

    def open_loop(self, stmts, due, burst_max: int,
                  seconds: float) -> Record:
        rec = Record()
        n = len(stmts)
        with jax.profiler.TraceAnnotation(SPAN_WINDOW):
            t0 = time.perf_counter()
            i = 0
            while i < n:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                if due[i] > now:
                    with jax.profiler.TraceAnnotation(SPAN_WAIT):
                        time.sleep(min(due[i], seconds) - now)
                    continue
                j = i + 1
                if stmts[i].query.kind == "scan":
                    while (
                        j < n
                        and j - i < burst_max
                        and due[j] <= now
                        and stmts[j].query.kind == "scan"
                        and stmts[j].phase == stmts[i].phase
                    ):
                        j += 1
                self.serve(stmts[i:j], rec, list(due[i:j]), t0)
                i = j
            rec.window_s = time.perf_counter() - t0
        return rec

    def closed_loop(self, stream, clients: int, seconds: float) -> Record:
        rec = Record()
        with jax.profiler.TraceAnnotation(SPAN_WINDOW):
            t0 = time.perf_counter()
            now = 0.0
            while now < seconds:
                burst = stream.take(clients)
                self.serve(burst, rec, [now] * len(burst), t0)
                now = time.perf_counter() - t0
            rec.window_s = now
        return rec
