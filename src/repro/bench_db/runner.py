"""Workload runner: drives a tuner over a workload on a simulated clock.

Timing model
------------
Latency is accounted in the engine's tuple-touch units converted at
``time_per_unit_ms``.  A query's latency is its execution cost plus
any in-query physical-design work its tuner performs (immediate-DL
population -- the latency-spike mechanism of Figures 2 and 7).

Background tuning cycles fire on a simulated-time schedule (the FAST /
MOD / SLOW frequencies of Section V-B).  Cycle work is charged to the
cumulative execution time *unless* the system is inside an idle window
(phase starts can be configured to throttle the client, Figure 6), in
which case the work rides on idle resources for free -- this is what
lets always-on tuners exploit idleness.

Phase boundaries can optionally drop every ad-hoc index ("diurnal"
mode, Figure 6: indexes have to be rebuilt every morning) -- tuner
*models* survive drops, which is exactly the predictive advantage.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field, fields, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.bench_db.workloads import Workload
from repro.core.build_service import BuildService
from repro.core.executor import Database
from repro.core.replica import ReplicaSet, ReplicaSetTuner
from repro.faults import FaultInjector, FaultSchedule
from repro.serving.admission import (
    backlog_depth,
    make_arrivals,
    next_burst,
    recent_arrival_gap_ms,
    slo_pressure,
)
from repro.serving.slo import SloReport, compute_slo

TUNING_FREQ_MS = {"fast": 100.0, "mod": 1000.0, "slow": 10000.0, "dis": None}


@dataclass
class ExecOptions:
    """How queries execute: storage partitioning + dispatch shape."""

    # >1: submit consecutive read scans through Database.execute_batch.
    read_batch_size: int = 1
    # >1: partition tables round-robin and fan scans out per shard
    # (engine).
    num_shards: int = 1
    # Mesh execution (parallel.mesh): None = auto, batched sharded
    # scans ride a shard_map device mesh whenever the local devices
    # can place the shard axis; False = force the single-device
    # stacked dispatch; True = REQUIRE a mesh -- a placement failure
    # raises instead of silently falling back.  mesh_query_axis > 1
    # additionally folds the 2-D query-batch axis over read bursts
    # (claims n_shards x mesh_query_axis devices).
    mesh: Optional[bool] = None
    mesh_query_axis: int = 1
    # Route batched scan dispatches through the Pallas kernel tier
    # (Database.execute_batch use_kernel).  Off by default: the
    # stacked vmap tier is the bit-exactness reference.
    use_kernel: bool = False


@dataclass
class TuningOptions:
    """When tuning cycles fire and how their build work is applied."""

    tuning_interval_ms: Optional[float] = 100.0  # None = disabled
    idle_at_phase_start_ms: float = 0.0          # throttled client window
    drop_indexes_at_phase_end: bool = False      # diurnal mode
    max_cycles_per_gap: int = 50                 # clamp catch-up storms
    # Async tuning pipeline (core.build_service).  None keeps the
    # legacy serialized schedule (tuning_cycle at burst boundaries).
    # "deterministic" routes every cycle through the decide/apply
    # split but drains all build quanta at the boundary -- bit-
    # identical results and accounting to serialized, for any shard
    # count (the invariance-test replay mode).  "overlap" drains
    # quanta on a concurrent build lane between the burst's batched
    # dispatches: build work no longer blocks queries (it is recorded
    # as tuner_overlapped_ms), undrained quanta carry over to the
    # next burst.
    async_tuning: Optional[str] = None  # None | 'deterministic' | 'overlap'
    build_quantum_pages: int = 8        # overlap-mode slice size
    # Overlap-mode backpressure: queue depth above which the build
    # lane escalates drains.
    build_queue_cap: int = 64
    # Shard-aware tuning: scans record per-shard page-access counters,
    # the tuner forecasts per-shard heat and sizes per-shard build
    # quanta by utility, and hybrid scans over diverged prefixes use
    # the engine's per-shard stitch.  False keeps every path
    # bit-identical to the legacy engine for any shard count.
    shard_aware_tuning: bool = False
    # Coverage-bitmap tuning (core.index.PageCoverage): crack_on_scan
    # lets every scan adopt up to crack_pages_per_scan of the pages it
    # just table-scanned into a matching building VAP index, and
    # index_decay lets the tuner clear the coldest covered pages when
    # the built footprint exceeds its storage budget.  Either flag
    # attaches a built-page bitmap to new VAP indexes (round-robin
    # layouts only) and their hybrid scans route through the masked
    # stitch.  Both off (the default) keeps every index on the legacy
    # prefix paths, bit-identical for any shard count.
    crack_on_scan: bool = False
    crack_pages_per_scan: int = 8
    index_decay: bool = False
    # Adaptive cycle sizing (overlap mode only): resize
    # TunerConfig.pages_per_cycle each cycle from the build lane's
    # measured EWMA throughput (BuildService.suggested_pages_per_cycle)
    # so cycle budgets track real build speed.  Never used under
    # serialized/deterministic scheduling -- the budget would depend on
    # wall clock, which breaks the bit-exact replay contract.
    adaptive_build_budget: bool = False


@dataclass
class ServingOptions:
    """Open-loop serving front end (repro.serving) + SLO machinery.

    Setting ``arrival_stream`` (or a burst deadline) switches
    run_workload into the open-loop driver: requests arrive on a
    seeded schedule ("uniform" | "poisson" | "bursty", mean
    inter-arrival = arrival_ms), read bursts close on
    read_batch_size OR burst_deadline_ms past the burst head's
    arrival (whichever fires first), and recorded latency is
    completion minus ARRIVAL -- queueing delay included.  The
    closed-loop path is bit-identical to pre-serving builds when
    both stay unset.  idle_at_phase_start_ms (a closed-loop client
    throttle) is ignored open-loop: idleness comes from the stream.
    """

    arrival_ms: float = 0.0  # open-loop client cadence (0 = closed loop)
    arrival_stream: Optional[str] = None
    arrival_seed: int = 0
    # Stream shape (bursty streams only; defaults reproduce the
    # admission layer's historical constants bit for bit):
    # peak_ratio = ON-state rate inflation, on_frac = ON-state duty
    # cycle, tenants > 1 superimposes that many independently seeded
    # per-tenant streams (each thinned to keep the aggregate mean) --
    # the multi-tenant mix, configurable without editing
    # serving/admission.py.
    arrival_peak_ratio: float = 8.0
    arrival_on_frac: float = 0.125
    arrival_tenants: int = 1
    burst_deadline_ms: Optional[float] = None
    # Per-query latency SLO: feeds the deadline-miss report
    # (RunResult.slo_report) and, with ``build_throttle``, the
    # load-aware throttle -- build drains are deferred to calmer
    # cycles while the backlog's estimated wait exceeds
    # ``slo_headroom`` of the SLO.  ``load_shed_tuning`` additionally
    # sheds the lowest-utility queued quanta down to build_queue_cap
    # under pressure (degrade tuning, never queries).
    slo_ms: Optional[float] = None
    slo_headroom: float = 0.5
    build_throttle: bool = False
    load_shed_tuning: bool = False
    # Anti-starvation bound on the throttle: after this many
    # consecutive deferred drain boundaries the next drain is forced
    # even under pressure.  Build work is what RESTORES capacity when
    # the backlog is the tuner's own fault (a phase shift caught
    # mid-storm leaves every query full-scanning); an unbounded
    # throttle turns that into a metastable spiral -- pressure defers
    # builds, queries stay slow, the backlog never drains, pressure
    # never clears.
    build_throttle_patience: int = 3


@dataclass
class ReplicaOptions:
    """Replica tier (core.replica): N data-identical replicas with
    cost-routed queries.  ``divergent_tuning`` clusters the workload
    window per cycle and points each replica's tuner at one cluster,
    so aggregate index capacity scales with replica count;  off, the
    replicas mirror (bit-identical to a single engine)."""

    n_replicas: int = 1
    divergent_tuning: bool = False


@dataclass
class FaultOptions:
    """Deterministic fault injection (repro.faults) + recovery.

    ``fault_schedule`` attaches a seeded ``FaultSchedule`` to the run:
    transient scan errors, straggler dispatch latency, build-quantum
    failures, and (replica tier only -- single-engine runs reject
    outage schedules) replica crash/rejoin epochs.  ``fault_recovery``
    selects the machinery under test: on (default), routing fails
    over DOWN replicas, rejoin replays the catch-up log, and failed
    build quanta retry with exponential backoff
    (``fault_build_backoff_ms * 2**attempt``, quarantine after
    ``fault_build_max_attempts`` failures); off is the no-recovery
    baseline -- crashes are permanent, statements routed to a dead
    replica drop, failed quanta are discarded.  ``None`` (the
    default) injects nothing and keeps every path bit-identical to
    the fault-free engine."""

    fault_schedule: Optional[FaultSchedule] = None
    fault_recovery: bool = True
    fault_build_max_attempts: int = 4
    fault_build_backoff_ms: float = 4.0


class RunConfig:
    """Run configuration, grouped by concern.

    The supported surface is the five option groups::

        RunConfig(
            execution=ExecOptions(num_shards=4),
            tuning=TuningOptions(async_tuning="overlap"),
            serving=ServingOptions(arrival_stream="bursty"),
            replica=ReplicaOptions(n_replicas=3),
            faults=FaultOptions(fault_schedule=schedule),
        )

    plus the globally shared ``time_per_unit_ms``.  Every legacy flat
    kwarg (``RunConfig(num_shards=4)``) still constructs the identical
    configuration through a compatibility shim -- it lands on the
    owning group and emits a ``DeprecationWarning`` -- and flat
    ATTRIBUTE access (``cfg.num_shards``) keeps working silently in
    both directions, so existing drivers and tests run unchanged.
    """

    def __init__(
        self,
        execution: Optional[ExecOptions] = None,
        tuning: Optional[TuningOptions] = None,
        serving: Optional[ServingOptions] = None,
        replica: Optional[ReplicaOptions] = None,
        faults: Optional[FaultOptions] = None,
        time_per_unit_ms: float = 1e-4,
        **flat,
    ):
        self.execution = execution if execution is not None else ExecOptions()
        self.tuning = tuning if tuning is not None else TuningOptions()
        self.serving = serving if serving is not None else ServingOptions()
        self.replica = replica if replica is not None else ReplicaOptions()
        self.faults = faults if faults is not None else FaultOptions()
        self.time_per_unit_ms = time_per_unit_ms
        for name, value in flat.items():
            group = _FLAT_TO_GROUP.get(name)
            if group is None:
                raise TypeError(
                    f"RunConfig got an unexpected keyword argument {name!r}"
                )
            warnings.warn(
                f"flat RunConfig kwarg {name!r} is deprecated; use "
                f"RunConfig({group}={type(getattr(self, group)).__name__}"
                f"({name}=...))",
                DeprecationWarning,
                stacklevel=2,
            )
            setattr(getattr(self, group), name, value)

    def __repr__(self) -> str:
        return (
            f"RunConfig(execution={self.execution!r}, "
            f"tuning={self.tuning!r}, serving={self.serving!r}, "
            f"replica={self.replica!r}, faults={self.faults!r}, "
            f"time_per_unit_ms={self.time_per_unit_ms!r})"
        )


# group field name -> owning RunConfig attribute, derived from the
# dataclasses so the shim can never drift from the groups.
_FLAT_TO_GROUP: Dict[str, str] = {
    f.name: group
    for group, cls in (
        ("execution", ExecOptions),
        ("tuning", TuningOptions),
        ("serving", ServingOptions),
        ("replica", ReplicaOptions),
        ("faults", FaultOptions),
    )
    for f in fields(cls)
}


def _flat_alias(group: str, name: str) -> property:
    def get(self):
        return getattr(getattr(self, group), name)

    def set_(self, value):
        setattr(getattr(self, group), name, value)

    return property(get, set_)


for _name, _group in _FLAT_TO_GROUP.items():
    setattr(RunConfig, _name, _flat_alias(_group, _name))
del _name, _group


@dataclass
class RunResult:
    latencies_ms: List[float] = field(default_factory=list)
    phases: List[int] = field(default_factory=list)
    cumulative_ms: float = 0.0        # queries + charged tuner work
    tuner_work_units: float = 0.0
    tuner_charged_ms: float = 0.0
    tuner_overlapped_ms: float = 0.0  # build work on the concurrent lane
    wall_s: float = 0.0
    index_counts: List[int] = field(default_factory=list)
    built_fraction: List[float] = field(default_factory=list)
    # build-lane telemetry (overlap mode): measured drain throughput
    # and how often backpressure escalated the drain frequency
    build_pages_per_ms: float = 0.0
    build_escalations: int = 0
    # adaptive cycle sizing: pages_per_cycle after the final resize
    # (0 when adaptive_build_budget is off or never fired)
    build_pages_per_cycle: int = 0
    # open-loop serving telemetry (arrival-stream mode only):
    # latencies_ms are completion-minus-arrival there, and the SLO
    # reporter slices them per phase (serving/slo.py)
    slo_report: Optional[SloReport] = None
    deadline_miss_rate: float = 0.0
    build_throttle_deferrals: int = 0   # drains deferred under pressure
    build_shed_quanta: int = 0          # quanta dropped by load shedding
    # Dispatch-strategy telemetry: execution tier -> queries served by
    # it (ScanEngine.TIERS: single / vmap-stacked / kernel /
    # shard_map).  Benchmarks assert the tier they mean to measure
    # instead of trusting a silent fallback.
    execution_tiers: Dict[str, int] = field(default_factory=dict)
    # Replica-tier telemetry (ReplicaOptions.n_replicas > 1): the
    # replica id every scan / read burst was routed to, in dispatch
    # order.  Empty when no replica tier was active.
    replica_routing: List[int] = field(default_factory=list)
    # Per-statement result triples (agg_sum, count, rows_modified) in
    # served order -- the chaos harness's correctness fingerprint: a
    # fault schedule with recovery on must reproduce the fault-free
    # run's list bit for bit (latency may shift, results never).
    results: List[Tuple[int, int, int]] = field(default_factory=list)
    # Fault-injection telemetry (FaultOptions.fault_schedule): served
    # fraction of offered statements, summed replica outage time on
    # the simulated clock, and injector event counters.  Healthy
    # defaults, so fault-free runs are unchanged.
    dropped_queries: int = 0
    availability: float = 1.0
    fault_downtime_ms: float = 0.0
    fault_scan_retries: int = 0
    fault_stragglers: int = 0
    fault_build_failures: int = 0
    fault_quarantined_builds: int = 0

    def percentile(self, p: float) -> float:
        """Latency percentile, 0.0 on empty runs (np.percentile raises
        on an empty sample -- write-only or zero-length workloads must
        not crash reporting)."""
        if not self.latencies_ms:
            return 0.0
        return float(np.percentile(self.latencies_ms, p))

    @property
    def mean_latency_ms(self) -> float:
        return float(np.mean(self.latencies_ms)) if self.latencies_ms else 0.0

    @property
    def p99_latency_ms(self) -> float:
        return self.percentile(99)

    @property
    def p999_latency_ms(self) -> float:
        return self.percentile(99.9)

    def summary(self) -> Dict[str, float]:
        if self.slo_report is not None:
            return {
                "queries": len(self.latencies_ms),
                "mean_latency_ms": round(self.mean_latency_ms, 5),
                "p50_ms": round(self.percentile(50), 5),
                "p99_ms": round(self.p99_latency_ms, 5),
                "p999_ms": round(self.p999_latency_ms, 5),
                "deadline_miss_rate": round(self.deadline_miss_rate, 5),
                "tuner_charged_ms": round(self.tuner_charged_ms, 3),
                "tuner_overlapped_ms": round(self.tuner_overlapped_ms, 3),
                "build_throttle_deferrals": self.build_throttle_deferrals,
                "build_shed_quanta": self.build_shed_quanta,
                "wall_s": round(self.wall_s, 2),
            }
        return {
            "queries": len(self.latencies_ms),
            "cumulative_ms": round(self.cumulative_ms, 3),
            "mean_latency_ms": round(self.mean_latency_ms, 5),
            "p99_latency_ms": round(self.p99_latency_ms, 5),
            "tuner_work_units": round(self.tuner_work_units, 1),
            "tuner_charged_ms": round(self.tuner_charged_ms, 3),
            "tuner_overlapped_ms": round(self.tuner_overlapped_ms, 3),
            "build_pages_per_ms": round(self.build_pages_per_ms, 2),
            "build_escalations": self.build_escalations,
            "wall_s": round(self.wall_s, 2),
        }


def run_workload(
    db: Database, tuner, workload: Workload, cfg: RunConfig
) -> RunResult:
    """Drive ``tuner`` over ``workload`` on the simulated clock.

    Dispatches to the closed-loop replay driver or (when an arrival
    stream / burst deadline is configured) the open-loop serving
    driver.  With ``cfg.replica.n_replicas > 1`` the database and
    tuner are first wrapped in the replica tier (core.replica): N
    data-identical replicas, scans cost-routed to the cheapest one,
    per-replica tuning lanes (divergent when
    ``cfg.replica.divergent_tuning``).  ``n_replicas=1`` never wraps,
    so the single-engine path is untouched.
    """
    rs: Optional[ReplicaSet] = None
    if cfg.replica.n_replicas > 1:
        # Reshard BEFORE cloning so every replica adopts the target
        # layout (the drivers' own reshard check then no-ops).
        if cfg.num_shards != getattr(db, "num_shards", 1):
            db.reshard(cfg.num_shards)
        rs = ReplicaSet(
            db,
            cfg.replica.n_replicas,
            divergent=cfg.replica.divergent_tuning,
        )
        tuner = ReplicaSetTuner(rs, tuner)
        db = rs
    injector: Optional[FaultInjector] = None
    schedule = cfg.faults.fault_schedule
    if schedule is not None:
        if schedule.outages and rs is None:
            raise ValueError(
                "FaultSchedule.outages require a replica tier "
                "(ReplicaOptions.n_replicas > 1): a single engine has "
                "nothing to fail over to"
            )
        injector = FaultInjector(
            schedule, recovery=cfg.faults.fault_recovery
        )
        db.fault_injector = injector  # fans out across replicas
    if cfg.arrival_stream is not None or cfg.burst_deadline_ms is not None:
        # Open-loop serving front end: requests arrive on their own
        # schedule, bursts close on size OR deadline, latency is
        # completion minus arrival.  A separate driver so the
        # closed-loop path stays bit-identical to pre-serving builds.
        res = _run_open_loop(db, tuner, workload, cfg)
    else:
        res = _run_closed_loop(db, tuner, workload, cfg)
    if rs is not None:
        res.replica_routing = list(rs.routed_queries)
    if injector is not None:
        res.fault_scan_retries = injector.scan_retries
        res.fault_stragglers = injector.straggler_events
        res.fault_build_failures = injector.build_failures
        if rs is not None:
            res.fault_downtime_ms = float(sum(rs.downtime_ms))
        offered = len(res.latencies_ms) + res.dropped_queries
        res.availability = (
            len(res.latencies_ms) / offered if offered else 1.0
        )
        if res.slo_report is not None:
            res.slo_report = replace(
                res.slo_report,
                availability=res.availability,
                downtime_ms=res.fault_downtime_ms,
                dropped=res.dropped_queries,
            )
    return res


def _run_closed_loop(
    db: Database, tuner, workload: Workload, cfg: RunConfig
) -> RunResult:
    """Single-core closed-loop timing model.

    Background cycle work first consumes accumulated *idle credit*
    (open-loop arrival gaps + explicit phase-start throttle windows);
    any overflow is non-preemptible and BLOCKS the next query -- that
    is the latency-spike mechanism of unbounded (holistic/value-based)
    population, while bounded VAP cycles typically fit in the credit.
    """
    if cfg.num_shards != getattr(db, "num_shards", 1):
        db.reshard(cfg.num_shards)
    if cfg.async_tuning not in (None, "deterministic", "overlap"):
        raise ValueError(f"async_tuning: {cfg.async_tuning!r}")

    # Async tuning pipeline: route cycles through the decide/apply
    # split.  Deterministic mode keeps the serialized quantum slices
    # (bit-exact replay); overlap mode sub-slices them so the engine
    # can drain fine-grained quanta between burst dispatches.
    db.shard_aware_tuning = bool(cfg.shard_aware_tuning)
    db.crack_on_scan = bool(cfg.crack_on_scan)
    db.crack_pages_per_scan = int(cfg.crack_pages_per_scan)
    db.index_decay = bool(cfg.index_decay)
    db.engine.mesh_mode = cfg.mesh
    db.engine.mesh_query_axis = max(int(cfg.mesh_query_axis), 1)
    overlap = cfg.async_tuning == "overlap"
    service = None
    if cfg.async_tuning is not None:
        service = BuildService(
            db,
            tuner,
            quantum_pages=cfg.build_quantum_pages if overlap else None,
            max_queue_depth=cfg.build_queue_cap if overlap else None,
            injector=getattr(db, "fault_injector", None),
            max_attempts=cfg.fault_build_max_attempts,
            backoff_ms=cfg.fault_build_backoff_ms,
        )

    res = RunResult()
    next_cycle_ms = (
        db.clock_ms + cfg.tuning_interval_ms
        if cfg.tuning_interval_ms
        else float("inf")
    )
    idle_until_ms = db.clock_ms + cfg.idle_at_phase_start_ms
    idle_credit_ms = cfg.idle_at_phase_start_ms
    blocking_ms = 0.0   # carried into the next query's latency
    prev_phase = 0

    # Adaptive cycle sizing: only the overlap lane measures real drain
    # throughput, and only its schedule may depend on the wall clock.
    adaptive = overlap and cfg.adaptive_build_budget and hasattr(tuner, "cfg")

    def resize_cycle_budget() -> None:
        """Feed the lane's measured EWMA throughput (pages/ms) back
        into TunerConfig.pages_per_cycle so cycle budgets track real
        build speed; clamped to [1, max_build_pages_per_cycle]."""
        pages = service.suggested_pages_per_cycle()
        if pages is None:
            return
        cap = tuner.cfg.max_build_pages_per_cycle
        tuner.cfg.pages_per_cycle = min(max(pages, 1), cap)
        res.build_pages_per_cycle = tuner.cfg.pages_per_cycle

    def run_cycle(idle: bool) -> float:
        """One due tuning cycle's *synchronous* work units."""
        if service is None:
            return tuner.tuning_cycle(idle=idle)
        if cfg.async_tuning == "deterministic":
            # Decide, then drain the whole queue at the boundary: the
            # exact serialized schedule through the split pipeline.
            return service.decide(idle=idle) + service.drain()
        if adaptive:
            resize_cycle_budget()
        return service.decide(idle=idle)  # overlap: quanta drain in-burst

    def overlap_quantum() -> float:
        """One drain opportunity on the concurrent build lane (the
        engine's between-dispatch hook): work is recorded but never
        enters the blocking path.  Applies ``drain_burst_size()``
        quanta -- one in steady state, more while backpressure says
        the queue is over its cap.  Returns the drained work-ms."""
        total_ms = 0.0
        for _ in range(service.drain_burst_size()):
            units = service.apply_next()
            if units <= 0.0:
                continue
            u_ms = units * cfg.time_per_unit_ms
            res.tuner_work_units += units
            res.tuner_overlapped_ms += u_ms
            total_ms += u_ms
        return total_ms

    def run_due_cycles():
        nonlocal next_cycle_ms, idle_credit_ms, blocking_ms
        if cfg.tuning_interval_ms is None:
            return
        fired = 0
        while db.clock_ms >= next_cycle_ms and fired < cfg.max_cycles_per_gap:
            idle = (db.clock_ms < idle_until_ms) or idle_credit_ms > 0.0
            work = run_cycle(idle)
            work_ms = work * cfg.time_per_unit_ms
            res.tuner_work_units += work
            absorbed = min(idle_credit_ms, work_ms)
            idle_credit_ms -= absorbed
            charged = work_ms - absorbed
            res.tuner_charged_ms += charged
            blocking_ms += charged
            db.clock_ms += max(charged, 1e-9)
            next_cycle_ms += cfg.tuning_interval_ms
            fired += 1
        if db.clock_ms >= next_cycle_ms:  # drop missed slots
            missed = (db.clock_ms - next_cycle_ms) // cfg.tuning_interval_ms
            next_cycle_ms += (int(missed) + 1) * cfg.tuning_interval_ms
        if overlap:
            # Idle windows feed the concurrent build lane too: drain
            # carryover quanta against the idle credit (the always-on
            # tuner's idle-resource exploitation, now spike-free).
            # Non-burst (single-dispatch) workloads need no boundary
            # special-case any more: Database.execute now exposes the
            # same between-dispatch drain point as the batched path,
            # and backpressure (drain_burst_size) escalates those
            # drains whenever the queue falls behind its cap.
            while idle_credit_ms > 0.0 and service.pending():
                idle_credit_ms = max(idle_credit_ms - overlap_quantum(), 0.0)

    def account(phase, q, stats):
        """Per-query bookkeeping shared by the single and batch paths."""
        nonlocal blocking_ms, idle_credit_ms
        if stats is None:
            # Fault-dropped statement (recovery-off routing hit a dead
            # replica): nothing was served, so only the drop counts;
            # pending blocking work carries to the next served query.
            res.dropped_queries += 1
            return
        extra_units = tuner.on_query(q, stats)
        extra_ms = extra_units * cfg.time_per_unit_ms
        db.clock_ms += extra_ms
        lat = stats.latency_ms + extra_ms + blocking_ms
        blocking_ms = 0.0
        res.latencies_ms.append(lat)
        res.phases.append(phase)
        res.cumulative_ms += lat
        res.results.append((stats.agg_sum, stats.count, stats.rows_modified))
        if stats.tier:
            res.execution_tiers[stats.tier] = (
                res.execution_tiers.get(stats.tier, 0) + 1
            )
        res.index_counts.append(len(db.indexes))
        fracs = [
            b.built_fraction(db.tables[b.desc.table])
            for b in db.indexes.values()
        ]
        res.built_fraction.append(float(np.mean(fracs)) if fracs else 0.0)
        if cfg.arrival_ms > 0.0 and lat < cfg.arrival_ms:
            gap = cfg.arrival_ms - lat
            db.clock_ms += gap
            idle_credit_ms += gap

    # Read bursts: consecutive batchable scans are staged and submitted
    # through the batched execution path in one dispatch.  Tuning
    # cycles fire at burst boundaries instead of between every query
    # (the burst is one uninterruptible unit of client work); mutations
    # and phase changes flush the stage first, preserving sequential
    # semantics.
    batch_n = max(int(cfg.read_batch_size), 1)
    staged: List[Tuple[int, object]] = []

    def flush_burst():
        if not staged:
            return
        run_due_cycles()
        stats_list = db.execute_batch(
            [q for _, q in staged], use_kernel=cfg.use_kernel
        )
        for (ph, q), stats in zip(staged, stats_list):
            account(ph, q, stats)
        staged.clear()

    import time as _time

    t_start = _time.perf_counter()
    if overlap:
        db.engine.after_dispatch = overlap_quantum
    try:
        for phase, q in workload:
            if phase != prev_phase:
                flush_burst()
                if cfg.drop_indexes_at_phase_end:
                    for name in list(db.indexes):
                        db.drop_index(name)
                idle_until_ms = db.clock_ms + cfg.idle_at_phase_start_ms
                idle_credit_ms += cfg.idle_at_phase_start_ms
                if cfg.idle_at_phase_start_ms > 0:
                    # traverse the idle window so due cycles fire inside
                    end = idle_until_ms
                    while db.clock_ms < end and cfg.tuning_interval_ms:
                        db.clock_ms = min(end, max(next_cycle_ms, db.clock_ms))
                        run_due_cycles()
                        if next_cycle_ms > end:
                            break
                    db.clock_ms = max(db.clock_ms, end)
                prev_phase = phase

            if batch_n > 1 and q.kind == "scan" and q.join_table is None:
                staged.append((phase, q))
                if len(staged) >= batch_n:
                    flush_burst()
                continue

            flush_burst()
            run_due_cycles()
            stats = db.execute(q)
            account(phase, q, stats)
        flush_burst()
    finally:
        if overlap:
            db.engine.after_dispatch = None
    if service is not None:
        res.build_pages_per_ms = service.pages_per_ms
        res.build_escalations = service.escalations
        res.fault_quarantined_builds = len(service.quarantined)
    res.wall_s = _time.perf_counter() - t_start
    return res


def _run_open_loop(
    db: Database, tuner, workload: Workload, cfg: RunConfig
) -> RunResult:
    """Open-loop serving driver (arrival-stream mode).

    Requests arrive on a seeded schedule (repro.serving.admission)
    instead of at the replay loop's cadence.  The admission layer
    forms read bursts dynamically -- close on ``read_batch_size`` OR
    ``burst_deadline_ms`` past the burst head's arrival, whichever
    fires first; mutations and phase changes flush the stage exactly
    like the closed loop -- and each burst goes through the existing
    ``Database.execute_batch`` path.  Recorded latency is completion
    minus ARRIVAL, so queueing delay is real: charged tuning work
    advances the clock and thereby delays every queued request
    (instead of being billed to one query's latency as closed-loop
    ``blocking_ms`` does).

    Graceful degradation under load: with ``build_throttle`` the
    deterministic build lane's boundary drains are deferred while the
    backlog's estimated wait (arrived-unserved depth x measured EWMA
    service time) exceeds ``slo_headroom`` of the SLO -- deferred
    quanta drain inside idle gaps, where their work is absorbed by
    idle credit; in overlap mode the concurrent lane is paused
    instead.  ``load_shed_tuning`` additionally drops the
    lowest-utility queued quanta down to ``build_queue_cap`` under
    pressure.  The system degrades by deferring or shedding *tuning
    work*; queries are never dropped.
    """
    if cfg.num_shards != getattr(db, "num_shards", 1):
        db.reshard(cfg.num_shards)
    if cfg.async_tuning not in (None, "deterministic", "overlap"):
        raise ValueError(f"async_tuning: {cfg.async_tuning!r}")

    db.shard_aware_tuning = bool(cfg.shard_aware_tuning)
    db.crack_on_scan = bool(cfg.crack_on_scan)
    db.crack_pages_per_scan = int(cfg.crack_pages_per_scan)
    db.index_decay = bool(cfg.index_decay)
    db.engine.mesh_mode = cfg.mesh
    db.engine.mesh_query_axis = max(int(cfg.mesh_query_axis), 1)
    overlap = cfg.async_tuning == "overlap"
    service = None
    if cfg.async_tuning is not None:
        service = BuildService(
            db,
            tuner,
            quantum_pages=cfg.build_quantum_pages if overlap else None,
            max_queue_depth=cfg.build_queue_cap if overlap else None,
            injector=getattr(db, "fault_injector", None),
            max_attempts=cfg.fault_build_max_attempts,
            backoff_ms=cfg.fault_build_backoff_ms,
        )

    items = list(workload)
    n = len(items)
    arrivals = db.clock_ms + make_arrivals(
        cfg.arrival_stream or "uniform",
        n,
        cfg.arrival_ms,
        seed=cfg.arrival_seed,
        peak_ratio=cfg.arrival_peak_ratio,
        on_frac=cfg.arrival_on_frac,
        tenants=cfg.arrival_tenants,
    )
    batch_n = max(int(cfg.read_batch_size), 1)
    batchable = np.array(
        [
            q.kind == "scan" and q.join_table is None and batch_n > 1
            for _, q in items
        ],
        bool,
    )
    phase_arr = np.array([p for p, _ in items], np.int64)

    res = RunResult()
    next_cycle_ms = (
        db.clock_ms + cfg.tuning_interval_ms
        if cfg.tuning_interval_ms
        else float("inf")
    )
    idle_credit_ms = 0.0
    served = 0                 # stream position: queries dispatched
    staged_end = 0             # end of the burst currently being formed
    ewma_service_ms = 0.0      # measured per-query service latency
    defer_streak = 0           # consecutive throttled drain boundaries
    prev_phase = 0

    def pressured() -> bool:
        # Overload = arrived requests that will STILL be queued after
        # the staged burst dispatches.  Counting the staged burst
        # itself would read every full batch as pressure and starve
        # the build lane for the whole run (one batch in flight is
        # the steady state, not a backlog).
        depth = backlog_depth(arrivals, max(served, staged_end), db.clock_ms)
        # Degraded mode: a lost replica shrinks serving capacity, so
        # the same backlog trips the throttle ladder earlier
        # (ReplicaSet.frac_up scales the SLO headroom; 1.0 -- plain
        # engines, healthy sets -- is the bit-identical no-op).
        frac_up = getattr(db, "frac_up", None)
        return slo_pressure(
            depth,
            ewma_service_ms,
            cfg.slo_ms,
            cfg.slo_headroom,
            capacity_frac=frac_up() if frac_up is not None else 1.0,
        )

    def defer_ok() -> bool:
        # Deferring build work is only safe when the backlog is
        # TRANSIENT: the measured service time keeps up with the
        # measured arrival rate, so the queue drains on its own and
        # the deferred charge lands in a later idle gap.  When the
        # server is underwater (service slower than arrivals), the
        # stale physical design IS the problem -- build through the
        # storm, exactly like the always-on lane.
        gap = recent_arrival_gap_ms(arrivals, db.clock_ms)
        return ewma_service_ms <= gap

    def shed_if_over_cap() -> None:
        if cfg.load_shed_tuning and service.pending() > cfg.build_queue_cap:
            res.build_shed_quanta += service.shed_lowest_utility(
                cfg.build_queue_cap
            )

    def run_cycle(idle: bool) -> float:
        nonlocal defer_streak
        if service is None:
            return tuner.tuning_cycle(idle=idle)
        work = service.decide(idle=idle)
        if overlap:
            return work        # quanta drain on the concurrent lane
        # Deterministic lane: boundary drain -- under backlog
        # pressure only the URGENT share drains (charged drain work
        # lands on every queued request's completion, so speculative
        # prebuild quanta wait for an idle gap, where idle credit
        # absorbs them).  Urgent work -- the hot index a storm is
        # full-scanning, top of the tuner's utility ranking -- builds
        # THROUGH the storm: it is what restores capacity, and
        # deferring it is a metastable spiral (slow queries keep the
        # backlog, the backlog keeps deferring the fix).  The
        # patience bound forces a full drain after too many deferred
        # boundaries, and a sustained (unsustainable-rate) storm
        # sheds the lowest-utility quanta past the backpressure cap.
        if (
            cfg.build_throttle
            and service.pending() > 0
            and pressured()
            and defer_streak < cfg.build_throttle_patience
        ):
            defer_streak += 1
            res.build_throttle_deferrals += 1
            work += service.drain_urgent()
            if not defer_ok():
                shed_if_over_cap()
            return work
        defer_streak = 0
        return work + service.drain()

    def overlap_quantum() -> float:
        total_ms = 0.0
        for _ in range(service.drain_burst_size()):
            units = service.apply_next()
            if units <= 0.0:
                continue
            u_ms = units * cfg.time_per_unit_ms
            res.tuner_work_units += units
            res.tuner_overlapped_ms += u_ms
            total_ms += u_ms
        return total_ms

    def run_due_cycles() -> None:
        nonlocal next_cycle_ms, idle_credit_ms
        if cfg.tuning_interval_ms is None:
            return
        fired = 0
        while db.clock_ms >= next_cycle_ms and fired < cfg.max_cycles_per_gap:
            work = run_cycle(idle_credit_ms > 0.0)
            work_ms = work * cfg.time_per_unit_ms
            res.tuner_work_units += work
            absorbed = min(idle_credit_ms, work_ms)
            idle_credit_ms -= absorbed
            charged = work_ms - absorbed
            res.tuner_charged_ms += charged
            db.clock_ms += max(charged, 1e-9)
            next_cycle_ms += cfg.tuning_interval_ms
            fired += 1
        if db.clock_ms >= next_cycle_ms:  # drop missed slots
            missed = (db.clock_ms - next_cycle_ms) // cfg.tuning_interval_ms
            next_cycle_ms += (int(missed) + 1) * cfg.tuning_interval_ms
        if overlap:
            # idle gaps feed the concurrent lane (carryover quanta
            # ride the credit) -- but not while the throttle holds it
            while idle_credit_ms > 0.0 and service.pending():
                if cfg.build_throttle and pressured():
                    break
                drained = overlap_quantum()
                if drained <= 0.0:
                    break
                idle_credit_ms = max(idle_credit_ms - drained, 0.0)

    def advance_to(target_ms: float) -> None:
        """Idle the server up to ``target_ms`` (waiting for arrivals
        or the burst timer): the gap accrues idle credit and due
        tuning cycles fire inside it, so background work lands in the
        window open-loop traffic actually leaves free."""
        nonlocal idle_credit_ms
        gap = target_ms - db.clock_ms
        if gap <= 0.0:
            return
        idle_credit_ms += gap
        if cfg.tuning_interval_ms is not None:
            while next_cycle_ms <= target_ms:
                db.clock_ms = max(db.clock_ms, next_cycle_ms)
                run_due_cycles()
                if db.clock_ms >= target_ms:
                    break
        db.clock_ms = max(db.clock_ms, target_ms)

    def account_open(
        ph: int, q, stats, arrival: float, completion: float
    ) -> None:
        lat = completion - arrival
        res.latencies_ms.append(lat)
        res.phases.append(ph)
        res.cumulative_ms += lat
        res.results.append((stats.agg_sum, stats.count, stats.rows_modified))
        if stats.tier:
            res.execution_tiers[stats.tier] = (
                res.execution_tiers.get(stats.tier, 0) + 1
            )
        res.index_counts.append(len(db.indexes))
        fracs = [
            b.built_fraction(db.tables[b.desc.table])
            for b in db.indexes.values()
        ]
        res.built_fraction.append(float(np.mean(fracs)) if fracs else 0.0)

    import time as _time

    t_start = _time.perf_counter()
    if overlap:
        db.engine.after_dispatch = overlap_quantum
    try:
        while served < n:
            start = served
            ph = int(phase_arr[start])
            if ph != prev_phase:
                if cfg.drop_indexes_at_phase_end:
                    for name in list(db.indexes):
                        db.drop_index(name)
                prev_phase = ph
            d = next_burst(
                arrivals,
                batchable,
                phase_arr,
                start,
                db.clock_ms,
                batch_n,
                cfg.burst_deadline_ms,
            )
            staged_end = d.end
            advance_to(d.dispatch_at)
            run_due_cycles()
            # Idle credit expires at dispatch: past idle time cannot
            # absorb future work (unlike the closed loop's banked
            # credit, which models a throttled client, not a live
            # stream).  Cycles that fire during a backlog therefore
            # get CHARGED -- which is exactly the pressure the
            # build throttle exists to relieve.
            idle_credit_ms = 0.0
            if overlap and cfg.build_throttle:
                # Same patience bound as the deterministic lane: a
                # pause held across too many dispatches would starve
                # the concurrent lane into the same spiral.
                was_paused = service.paused
                service.paused = (
                    pressured()
                    and defer_ok()
                    and defer_streak < cfg.build_throttle_patience
                )
                if service.paused:
                    defer_streak += 1
                    if not was_paused:
                        res.build_throttle_deferrals += 1
                    shed_if_over_cap()
                else:
                    defer_streak = 0
            burst = items[start:d.end]
            base = db.clock_ms
            if len(burst) == 1 and not batchable[start]:
                stats_list = [db.execute(burst[0][1])]
            else:
                stats_list = db.execute_batch(
                    [q for _, q in burst], use_kernel=cfg.use_kernel
                )
            cum = 0.0
            for k, ((bph, q), stats) in enumerate(zip(burst, stats_list)):
                if stats is None:
                    # Fault-dropped statement (recovery-off routing hit
                    # a dead replica): no service time, no latency
                    # sample -- only the availability hit.
                    res.dropped_queries += 1
                    continue
                extra_units = tuner.on_query(q, stats)
                extra_ms = extra_units * cfg.time_per_unit_ms
                db.clock_ms += extra_ms
                service_ms = stats.latency_ms + extra_ms
                cum += service_ms
                a = 0.25
                ewma_service_ms = (
                    service_ms
                    if ewma_service_ms == 0.0
                    else (1.0 - a) * ewma_service_ms + a * service_ms
                )
                account_open(
                    bph, q, stats, float(arrivals[start + k]), base + cum
                )
            served = d.end
    finally:
        if overlap:
            db.engine.after_dispatch = None
    if service is not None:
        res.build_pages_per_ms = service.pages_per_ms
        res.build_escalations = service.escalations
        res.build_shed_quanta = service.shed_quanta
        res.fault_quarantined_builds = len(service.quarantined)
    res.slo_report = compute_slo(res.latencies_ms, res.phases, cfg.slo_ms)
    res.deadline_miss_rate = res.slo_report.overall.miss_rate
    res.wall_s = _time.perf_counter() - t_start
    return res
