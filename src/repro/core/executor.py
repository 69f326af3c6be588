"""Query execution: the Database facade over the planner/engine split.

Owns the tables, built indexes and layout state of one database, and
executes benchmark statements, returning *measured* statistics in the
same tuple-touch units the what-if cost model estimates in (see
``cost_model``).  This is the substrate every indexing approach runs
on -- only the decision logic and population scheme differ between
tuners, exactly as in the paper's DBMS-X integration.

The execution core is split in two (PR 2):

* ``core.planner.QueryPlanner`` -- access-path choice, selectivity
  estimation and cost accounting; pure Python, no array dispatch.
* ``core.engine.ScanEngine``    -- jitted scan dispatch over plain or
  sharded storage; on a ``ShardedTable`` every scan fans out per shard
  and tree-reduces per-query aggregates.

``Database`` wires plans to dispatches, replays cost/clock/monitor
accounting, and routes mutations to the storage layout's mutators.
Pass ``num_shards > 1`` (or call ``reshard``) to partition every table
round-robin by page; results and accounting are bit-identical across
shard counts (tests/test_sharded_engine.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm
from repro.core.cost_model import IndexDescriptor
from repro.core.engine import ScanEngine
from repro.core.index import (
    ShardedIndex,
    ShardedVbpState,
    advance_build,
    advance_build_shard,
    build_page_list,
    coverage_from_state,
    eligible_global_pages,
    make_index,
    make_sharded_index,
    make_sharded_vbp,
    make_vbp,
    shard_full_pages,
    sharded_vbp_populate_subdomain,
    vbp_invalidate_coverage,
    vbp_n_entries,
    vbp_populate_subdomain,
)
from repro.core.layout import LayoutState, scan_width_factor
from repro.core.monitor import QueryRecord, WorkloadMonitor
from repro.core.planner import (
    HYBRID_SELECTIVITY_CUTOFF,  # noqa: F401
    BuiltIndex,
    IntervalUnion,
    QueryPlanner,
    scan_cost,
)
from repro.core.table import (
    ShardedTable,
    Table,
    conj_predicate_mask,
    copy_table,
    insert_rows,
    round_robin_layout,
    shard_table,
    sharded_insert_rows,
    sharded_update_rows,
    table_bytes,
    unshard_table,
    update_rows,
    visible_mask,
)
from repro.obs import host_read, span


class _Extent(NamedTuple):
    """What a built fraction reads of a table (``BuiltIndex``):
    the append watermark and the page size."""

    n_rows: object
    page_size: int


@dataclass
class Query:
    kind: str  # 'scan' | 'update' | 'insert'
    table: str
    attrs: Tuple[int, ...] = ()
    los: Tuple[int, ...] = ()
    his: Tuple[int, ...] = ()
    agg_attr: int = 2
    proj_attrs: Tuple[int, ...] = ()
    set_attrs: Tuple[int, ...] = ()
    set_vals: Tuple[int, ...] = ()
    rows: Optional[np.ndarray] = None  # INSERT payload
    # HIGH-S equi-join: R.join_attr == S.join_inner_attr
    join_table: Optional[str] = None
    join_attr: int = 0
    join_inner_attr: int = 0
    template: str = ""

    @property
    def accessed_attrs(self) -> Tuple[int, ...]:
        return tuple(
            sorted(
                set(self.attrs)
                | set(self.proj_attrs)
                | ({self.agg_attr} if self.kind == "scan" else set())
                | set(self.set_attrs)
            )
        )


@dataclass
class ExecStats:
    cost_units: float  # tuple-touch units (simulated work)
    latency_ms: float  # simulated latency
    used_index: bool
    agg_sum: int = 0
    count: int = 0
    rows_modified: int = 0
    populate_units: float = 0.0  # in-query VBP population work (spikes)
    # Per-shard pages the access path touched (shard-aware tuning only).
    shard_pages: Tuple[int, ...] = ()
    # Execution tier of the dispatch that served this query
    # (ScanEngine.TIERS).
    tier: str = ""


class Database:
    """Tables + index configuration + layout + monitor + simulated clock."""

    def __init__(
        self,
        tables: Dict[str, object],
        time_per_unit_ms: float = 1e-4,
        monitor_window: int = 256,
        monitor_max_age_ms: float | None = None,
        num_shards: int = 1,
    ):
        self.tables: Dict[str, object] = dict(tables)
        # Ownership (core/table.py's rule for the write programs): the
        # table object this database's own last write returned, by
        # name.  Only that table is written in place; any other one
        # (given to the constructor, handed out by ``share_tables``,
        # put into ``tables`` from outside) is copied once first.
        self._owned: Dict[str, Table] = {}
        self.num_shards = 1
        self.indexes: Dict[str, BuiltIndex] = {}
        self.layouts: Dict[str, LayoutState] = {
            name: LayoutState(n_attrs=t.n_attrs, n_pages=t.n_pages)
            for name, t in self.tables.items()
        }
        self.monitor = WorkloadMonitor(
            window=monitor_window, max_age_ms=monitor_max_age_ms
        )
        self.clock_ms: float = 0.0
        self.time_per_unit_ms = time_per_unit_ms
        self.update_cap = 512  # max rows materialised per UPDATE
        # Shard-aware tuning (RunConfig.shard_aware_tuning): when set,
        # scans record per-shard page-access counters and build quanta
        # may target single shards.  ``pershard_built`` tracks indexes
        # whose shard-local prefixes have diverged from the global
        # round-robin prefix -- their hybrid scans must use the
        # per-shard stitch (planner._needs_pershard_stitch).
        self.shard_aware_tuning: bool = False
        self.pershard_built: set = set()
        # Coverage-bitmap tuning: ``crack_on_scan`` lets a scan adopt
        # pages it just table-scanned into a matching building VAP
        # index (bitmap coverage retires the page-order constraint);
        # ``index_decay`` lets the tuner drop cold built pages under
        # the storage cap.  Both default off -- flag-off runs never
        # attach a PageCoverage, so every index keeps the legacy
        # prefix paths bit-for-bit.
        self.crack_on_scan: bool = False
        self.crack_pages_per_scan: int = 8
        self.index_decay: bool = False
        # Fault injection (repro.faults.FaultInjector): consulted once
        # per scan dispatch for transient-error retries and straggler
        # latency.  Faults perturb latency ONLY -- results, costs and
        # visibility are computed before the perturbation, so a seeded
        # schedule degrades responsiveness without ever changing what
        # a query returns.  None (the default) skips every consult.
        self.fault_injector = None
        self._round_robin_cache: Dict[str, bool] = {}
        self._zone_maps: Dict[tuple, tuple] = {}
        self.planner = QueryPlanner(self)
        self.engine = ScanEngine()
        counts = {
            t.n_shards
            for t in self.tables.values()
            if isinstance(t, ShardedTable)
        }
        if num_shards > 1:
            self.reshard(num_shards)
        elif counts:
            # Adopt pre-sharded tables as-is when the layout is
            # uniform; only rebuild to normalise a mixed layout.
            target = max(counts)
            if counts == {target} and all(
                isinstance(t, ShardedTable) for t in self.tables.values()
            ):
                self.num_shards = target
            else:
                self.reshard(target)

    # ------------------------------------------------------------------
    # Storage layout
    # ------------------------------------------------------------------
    def reshard(self, num_shards: int) -> None:
        """Re-partition every table round-robin over ``num_shards``.

        Built ad-hoc indexes are dropped (their rid spaces change);
        tuners rebuild them, exactly like the diurnal index drop.
        Layout state survives -- page ids are global either way.
        """
        for name in list(self.indexes):
            self.drop_index(name)
        for name, t in self.tables.items():
            if isinstance(t, ShardedTable):
                t = unshard_table(t)
            self.tables[name] = (
                shard_table(t, num_shards) if num_shards > 1 else t
            )
        self.num_shards = num_shards
        self._owned.clear()
        self._round_robin_cache.clear()
        self._zone_maps.clear()

    def share_tables(self) -> Dict[str, object]:
        """The tables, for another holder (a replica database): this
        database gives up their ownership, so neither side writes them
        in place and each copies a table before its first write."""
        self._owned.clear()
        return dict(self.tables)

    def _writable(self, name: str) -> Table:
        """Table ``name`` as this database may write it in place: the
        table itself if the database owns it, else a copy made once by
        ``copy_table`` inside a ``repro.table.own`` span."""
        t = self.tables[name]
        if self._owned.get(name) is not t:
            with span("repro.table.own", table=name, bytes=table_bytes(t)):
                t = copy_table(t)
            self.tables[name] = self._owned[name] = t
        return t

    def _store(self, name: str, t) -> None:
        """Install the table a write returned; a plain one is owned."""
        self.tables[name] = t
        if isinstance(t, Table):
            self._owned[name] = t

    def table_is_round_robin(self, name: str) -> bool:
        """Cached: does ``name``'s shard layout follow the round-robin
        page map?  Mutators preserve the property either way, so the
        answer only changes on reshard (which clears the cache)."""
        got = self._round_robin_cache.get(name)
        if got is None:
            t = self.tables[name]
            got = not isinstance(t, ShardedTable) or round_robin_layout(t)
            self._round_robin_cache[name] = got
        return got

    # ------------------------------------------------------------------
    # Index configuration actions (used by tuners)
    # ------------------------------------------------------------------
    def create_index(self, desc: IndexDescriptor, scheme: str) -> BuiltIndex:
        t = self.tables[desc.table]
        if desc.name in self.indexes:
            return self.indexes[desc.name]
        bi = BuiltIndex(desc=desc, scheme=scheme, created_ms=self.clock_ms)
        sharded = isinstance(t, ShardedTable)
        if scheme in ("vap", "full"):
            bi.vap = (
                make_sharded_index(t) if sharded else make_index(t.capacity)
            )
            self.ensure_coverage(bi)
        else:
            bi.vbp = make_sharded_vbp(t) if sharded else make_vbp(t.capacity)
            bi.cov_union = IntervalUnion()
        self.indexes[desc.name] = bi
        return bi

    def drop_index(self, name: str) -> None:
        self.indexes.pop(name, None)
        self.pershard_built.discard(name)

    def indexes_on(self, table: str):
        return [b for b in self.indexes.values() if b.desc.table == table]

    def total_index_bytes(self) -> float:
        return sum(b.size_bytes() for b in self.indexes.values())

    def ensure_coverage(self, bi: BuiltIndex) -> bool:
        """Attach a built-page bitmap to a VAP index when coverage
        tuning is enabled (crack_on_scan / index_decay) and the table
        layout supports global page ids (round-robin).  Seeds from the
        index's current built prefix, so attaching mid-build is safe;
        once attached, ALL builds must route through ``vap_build_step``
        (which switches to ``build_page_list``) -- replaying
        ``advance_build`` over covered pages would duplicate entries.
        """
        if bi.coverage is not None:
            return True
        if (
            bi.scheme != "vap"
            or not (self.crack_on_scan or self.index_decay)
            or not self.table_is_round_robin(bi.desc.table)
        ):
            return False
        bi.coverage = coverage_from_state(bi.vap, self.tables[bi.desc.table])
        return True

    def coverage_pages_left(self, bi: BuiltIndex) -> int:
        """Uncovered fully-populated pages of a bitmap-mode index --
        the coverage analogue of ``index.build_pages_remaining``."""
        t = self.tables[bi.desc.table]
        eligible = eligible_global_pages(t)
        return int((~bi.coverage.built[eligible]).sum())

    def zone_map(self, table: str, attr: int):
        """Per-GLOBAL-page (min, max) of ``attr`` over the fully
        populated pages -- the hot-range build planner's page-pruning
        metadata.  Advisory only (it sizes and orders build quanta,
        never results), so dead row versions are included and the
        ranges are conservative.  Cached per (table, attr); any
        mutation of the table or a reshard invalidates.  Pages outside
        the full watermark get an empty (max < min) range."""
        key = (table, attr)
        got = self._zone_maps.get(key)
        if got is not None:
            return got
        t = self.tables[table]
        psz = t.page_size
        if isinstance(t, ShardedTable):
            n_global = t.n_shards * max(x.n_pages for x in t.shards)
            mins = np.full(n_global, np.iinfo(np.int32).max, np.int64)
            maxs = np.full(n_global, np.iinfo(np.int32).min, np.int64)
            for s, sh in enumerate(t.shards):
                full = int(sh.n_rows) // psz
                if full == 0:
                    continue
                vals = np.asarray(sh.data[:full, :, attr])
                gids = s + t.n_shards * np.arange(full)
                mins[gids] = vals.min(axis=1)
                maxs[gids] = vals.max(axis=1)
        else:
            full = int(t.n_rows) // psz
            mins = np.full(t.n_pages, np.iinfo(np.int32).max, np.int64)
            maxs = np.full(t.n_pages, np.iinfo(np.int32).min, np.int64)
            if full:
                vals = np.asarray(t.data[:full, :, attr])
                mins[:full] = vals.min(axis=1)
                maxs[:full] = vals.max(axis=1)
        got = (mins, maxs)
        self._zone_maps[key] = got
        return got

    # Planner delegation (kept as methods for tuner/baseline callers).
    def _estimate_selectivity(self, q: Query) -> float:
        return self.planner.estimate_selectivity(q)

    def _choose_index(self, q: Query) -> Optional[BuiltIndex]:
        return self.planner.choose_index(q)

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------
    def execute(self, q: Query, observe: bool = True) -> ExecStats:
        with span("repro.execute", kind=q.kind):
            if q.kind == "scan":
                stats = self._exec_scan(q)
            elif q.kind == "update":
                stats = self._exec_update(q)
            elif q.kind == "insert":
                stats = self._exec_insert(q)
            else:
                raise ValueError(q.kind)
            # Non-burst drain point: single-dispatch workloads feed the
            # concurrent build lane exactly like the batched path does
            # between group dispatches (no-op unless overlap scheduling
            # installed a hook; the statement's timed region is closed).
            self.engine.dispatch_complete()
            self.clock_ms += stats.latency_ms
            if observe:
                n_rows = int(
                    host_read(
                        self.tables[q.table].n_rows, "executor.execute.n_rows"
                    )
                )
                self.monitor.observe(
                    QueryRecord(
                        kind=q.kind,
                        table=q.table,
                        pred_attrs=tuple(q.attrs),
                        accessed_attrs=q.accessed_attrs,
                        selectivity=(
                            stats.count / max(n_rows, 1)
                            if q.kind == "scan"
                            else stats.rows_modified / max(n_rows, 1)
                        ),
                        tuples_scanned=int(stats.cost_units),
                        used_index=stats.used_index,
                        rows_modified=stats.rows_modified,
                        ts_ms=self.clock_ms,
                        template=q.template,
                        shard_pages=stats.shard_pages,
                        pred_ranges=tuple(zip(q.attrs, q.los, q.his)),
                    )
                )
                if q.join_table is not None:
                    # The inner side of an equi-join is an indexable access
                    # path too (HIGH-S benefits from join-attribute indexes).
                    n_inner = int(self.tables[q.join_table].n_rows)
                    self.monitor.observe(
                        QueryRecord(
                            kind="scan",
                            table=q.join_table,
                            pred_attrs=(q.join_inner_attr,),
                            selectivity=min(
                                stats.count / max(n_inner, 1), 1.0
                            ),
                            tuples_scanned=n_inner,
                            used_index=stats.used_index,
                            rows_modified=0,
                            ts_ms=self.clock_ms,
                            template=q.template + ":join",
                        )
                    )
            return stats

    def _exec_scan(self, q: Query) -> ExecStats:
        t = self.tables[q.table]
        layout = self.layouts[q.table]
        plan = self.planner.plan_scan(q)
        bi = plan.index

        with span(
            "repro.executor.dispatch",
            path=plan.path,
            index=bi.desc.name if bi is not None else "",
            queries=1,
        ) as sp:
            los = jnp.asarray([q.los], jnp.int32)
            his = jnp.asarray([q.his], jnp.int32)
            r = self.engine.scan_batch(
                t,
                plan.path,
                plan.index_state,
                plan.key_attrs,
                tuple(q.attrs),
                los,
                his,
                self.clock_ms_i32()[None],
                q.agg_attr,
                coverage=plan.pinned_coverage,
            )
            sp.set_metadata(tier=self.engine.last_tier or "")

        if plan.path == "table":
            start_page, entries = 0, 0.0
        elif plan.path in ("hybrid", "hybrid_ps", "hybrid_masked"):
            start_page = int(
                host_read(r.start_page, "executor.scan.start")[0]
            )
            entries = float(
                int(host_read(r.entries_probed, "executor.scan.entries")[0])
            )
        else:  # pure index scan: no table pages touched
            start_page = t.n_pages
            entries = float(
                int(host_read(r.entries_probed, "executor.scan.entries")[0])
            )
        cost = scan_cost(
            layout,
            q.accessed_attrs,
            t.page_size,
            int(host_read(r.pages_scanned, "executor.scan.pages")[0]),
            entries,
            start_page,
        )
        populate = self._crack_adopt(q, plan, start_page)
        cost += populate
        used = bi is not None
        if used:
            bi.last_used_ms = self.clock_ms

        count = int(host_read(r.count, "executor.scan.count")[0])
        if q.join_table is not None:
            count, join_cost, join_used = self._exec_join(q)
            cost += join_cost
            used = used or join_used
        lat_ms = cost * self.time_per_unit_ms
        if self.fault_injector is not None:
            # Transient errors re-issue the dispatch (its latency is
            # paid again per retry); stragglers add flat extra time.
            # Guarded so a fault-free draw leaves lat_ms untouched
            # bit for bit.
            retries, extra_ms = self.fault_injector.scan_fault()
            if retries or extra_ms:
                lat_ms += retries * lat_ms + extra_ms
        return ExecStats(
            cost_units=cost,
            latency_ms=lat_ms,
            used_index=used,
            agg_sum=int(host_read(r.agg_sum, "executor.scan.sum")[0]),
            count=count,
            populate_units=populate,
            shard_pages=self._shard_pages_of(t, plan),
            tier=self.engine.last_tier or "",
        )

    def _crack_adopt(self, q: Query, plan, start_page: int) -> float:
        """Crack-on-scan: adopt up to ``crack_pages_per_scan`` of the
        pages this scan just table-scanned into a matching building
        VAP index (``build_page_list`` + coverage bit flips).  The
        extraction+merge work piggybacks on the triggering query, so
        the returned units are charged to its cost and reported as
        ``populate_units`` -- the VAP twist on cracking's adaptive
        population.  Only bitmap-mode indexes adopt: the legacy prefix
        invariant forbids out-of-order entries."""
        if not self.crack_on_scan or plan.path not in (
            "table",
            "hybrid",
            "hybrid_ps",
            "hybrid_masked",
        ):
            return 0.0
        bi = plan.index
        if bi is None:
            # Full table scans still crack: any building bitmap index
            # whose leading key the predicate constrains may adopt.
            for cand in self.indexes_on(q.table):
                if (
                    cand.scheme == "vap"
                    and cand.building
                    and cand.coverage is not None
                    and cm.index_matches(cand.desc, q.table, q.attrs)
                ):
                    bi = cand
                    break
        if (
            bi is None
            or bi.scheme != "vap"
            or not bi.building
            or bi.coverage is None
        ):
            return 0.0
        t = self.tables[q.table]
        cov = bi.coverage
        eligible = eligible_global_pages(t)
        # Pages the scan actually visited: the table-scan region starts
        # at the stitch point (0 for full scans; for the masked stitch
        # every uncovered page sits at or past the covered prefix).
        open_pages = eligible[(eligible >= start_page) & ~cov.built[eligible]]
        take = open_pages[: self.crack_pages_per_scan]
        if take.size == 0:
            return 0.0
        bi.vap = build_page_list(bi.vap, t, bi.desc.key_attrs, take)
        cov.set_pages(take)
        if cov.built[eligible].all():
            bi.complete = True
            bi.building = False
        return float(take.size * t.page_size)

    def _shard_pages_of(self, t, plan) -> Tuple[int, ...]:
        """Per-shard pages the planned access path table-scans -- the
        monitor's shard-heat signal (advisory: it sizes build quanta,
        never results or accounting, so the cheap host-side form
        ignores the transient rho_m component of the stitch)."""
        if not (self.shard_aware_tuning and isinstance(t, ShardedTable)):
            return ()
        psz = t.page_size
        lused = [(int(x.n_rows) + psz - 1) // psz for x in t.shards]
        if plan.path == "table":
            return tuple(lused)
        if plan.path == "hybrid_masked" and plan.pinned_coverage is not None:
            cov = plan.pinned_coverage
            S = len(t.shards)
            return tuple(
                int(u - cov.built_host[s + S * np.arange(u)].sum())
                for s, u in enumerate(lused)
            )
        state = plan.index_state
        if plan.path in ("hybrid", "hybrid_ps") and isinstance(
            state, ShardedIndex
        ):
            return tuple(
                max(u - int(ix.built_pages), 0)
                for u, ix in zip(lused, state.shards)
            )
        return (0,) * len(t.shards)  # pure index scan

    # ------------------------------------------------------------------
    # Batched execution (read bursts)
    # ------------------------------------------------------------------
    def execute_batch(
        self, queries, observe: bool = True, use_kernel: bool = False
    ):
        """Execute a burst of queries, batching compatible read scans.

        Scans that share (table, attrs, agg_attr) and access path are
        evaluated in ONE dispatch (``batched_*_scan``; with
        ``use_kernel`` the table-scan and hybrid groups go through the
        Pallas multi-query kernel via the ops layer; on sharded tables
        each group fans out per shard) instead of one dispatch per
        query.  Results and accounting are bit-identical to
        ``[self.execute(q) for q in queries]``:

        * A maximal run of consecutive batchable scans forms one
          burst, executed against the snapshot at burst start.  Every
          version timestamp in the table predates that snapshot, and
          reads do not mutate, so each query sees exactly the
          visibility it would have seen at its own (later) per-query
          snapshot.
        * Cost, latency, simulated-clock advancement and monitor
          observations are replayed per query, in order, from the
          per-query batch results.
        * Non-batchable statements (updates, inserts, joins) flush
          the pending burst and run through ``execute``, so mutations
          interleaved with reads keep sequential semantics.

        Returns the list of per-query ``ExecStats`` in input order.
        """
        out: list = [None] * len(queries)
        pending: list = []  # [(position, query)]

        def flush():
            if pending:
                self._exec_scan_burst(pending, out, observe, use_kernel)
                pending.clear()

        with span("repro.execute_batch", statements=len(queries)):
            for i, q in enumerate(queries):
                if q.kind == "scan" and q.join_table is None:
                    pending.append((i, q))
                else:
                    flush()
                    out[i] = self.execute(q, observe=observe)
            flush()
        return out

    def _exec_scan_burst(
        self, pending, out, observe: bool, use_kernel: bool
    ) -> None:
        """Plan, group and execute one burst of batchable scans."""
        # Plan each query exactly like _exec_scan would, then group by
        # (table, attrs, agg_attr, access path, index).  Plans cannot
        # change mid-burst: reads never mutate tables, and the catalog
        # snapshot (double buffer) keeps every plan resolving against
        # the burst-start index states even while the async build
        # service advances ``built_pages`` between the group
        # dispatches below.
        self.planner.begin_snapshot()
        try:
            groups: Dict[tuple, list] = {}
            for pos, q in pending:
                plan = self.planner.plan_scan(q)
                key = (q.table, tuple(q.attrs), q.agg_attr) + plan.group_key
                groups.setdefault(key, []).append((pos, q, plan))

            # Run each group in one dispatch (one fan-out per shard when
            # the table is sharded); gather per-position raw rows.
            ts = self.clock_ms_i32()
            # pos -> (sum, count, pages, entries, start_page, tier)
            raw: Dict[int, tuple] = {}
            # pos -> (retries, straggler-share ms).  One fault draw
            # per GROUP dispatch (the dispatch is the fault domain);
            # its straggler latency is shared evenly across members.
            fault_by_pos: Dict[int, tuple] = {}
            for group_key, members in groups.items():
                table_name, attrs, agg_attr, path, idx = group_key
                t = self.tables[table_name]
                plan = members[0][2]
                with span(
                    "repro.executor.dispatch",
                    path=path,
                    index=idx or "",
                    queries=len(members),
                ) as sp:
                    los = jnp.asarray(
                        [q.los for _, q, _ in members], jnp.int32
                    )
                    his = jnp.asarray(
                        [q.his for _, q, _ in members], jnp.int32
                    )
                    tss = jnp.full((len(members),), ts, jnp.int32)
                    r = self.engine.scan_batch(
                        t,
                        plan.path,
                        plan.index_state,
                        plan.key_attrs,
                        attrs,
                        los,
                        his,
                        tss,
                        agg_attr,
                        use_kernel=use_kernel,
                        coverage=plan.pinned_coverage,
                    )
                    tier = self.engine.last_tier or ""
                    sp.set_metadata(tier=tier)
                # Drain point between this group's dispatch and the
                # next, outside its span: quantum work is the build
                # lane's, not the dispatch's.
                self.engine.dispatch_complete()
                if self.fault_injector is not None:
                    retries, extra_ms = self.fault_injector.scan_fault()
                    if retries or extra_ms:
                        share = extra_ms / len(members)
                        for pos, _q, _plan in members:
                            fault_by_pos[pos] = (retries, share)
                agg_sums = host_read(r.agg_sum, "executor.burst.sum")
                counts = host_read(r.count, "executor.burst.count")
                pages = host_read(r.pages_scanned, "executor.burst.pages")
                entries = host_read(r.entries_probed, "executor.burst.entries")
                starts = host_read(r.start_page, "executor.burst.start")
                for k, (pos, _q, _plan) in enumerate(members):
                    raw[pos] = (
                        int(agg_sums[k]),
                        int(counts[k]),
                        int(pages[k]),
                        int(entries[k]),
                        int(starts[k]),
                        tier,
                    )
        finally:
            self.planner.end_snapshot()

        # Accounting replay in input order (host-side, same arithmetic
        # and clock/monitor trajectory as the per-query loop).
        plan_by_pos = {
            pos: plan for ms in groups.values() for pos, _q, plan in ms
        }
        for pos, q in pending:
            rec = raw[pos]
            agg_sum, count, n_pages, n_entries, start_page, tier = rec
            t = self.tables[q.table]
            layout = self.layouts[q.table]
            plan_q = plan_by_pos[pos]
            bi_q = plan_q.index
            cost = scan_cost(
                layout,
                q.accessed_attrs,
                t.page_size,
                n_pages,
                float(n_entries),
                start_page,
            )
            # Crack adoption replays per query, in order, exactly like
            # the sequential loop; results stay burst-consistent
            # because every dispatch above ran against the pinned
            # burst-start coverage views.
            populate = self._crack_adopt(q, plan_q, start_page)
            cost += populate
            used = bi_q is not None
            if used:
                bi_q.last_used_ms = self.clock_ms
            lat_ms = cost * self.time_per_unit_ms
            if pos in fault_by_pos:
                retries, share = fault_by_pos[pos]
                lat_ms += retries * lat_ms + share
            stats = ExecStats(
                cost_units=cost,
                latency_ms=lat_ms,
                used_index=used,
                agg_sum=agg_sum,
                count=count,
                populate_units=populate,
                shard_pages=self._shard_pages_of(t, plan_q),
                tier=tier,
            )
            self.clock_ms += stats.latency_ms
            if observe:
                n_rows = int(host_read(t.n_rows, "executor.account.n_rows"))
                self.monitor.observe(
                    QueryRecord(
                        kind="scan",
                        table=q.table,
                        pred_attrs=tuple(q.attrs),
                        accessed_attrs=q.accessed_attrs,
                        selectivity=stats.count / max(n_rows, 1),
                        tuples_scanned=int(stats.cost_units),
                        used_index=stats.used_index,
                        rows_modified=0,
                        ts_ms=self.clock_ms,
                        template=q.template,
                        shard_pages=stats.shard_pages,
                        pred_ranges=tuple(zip(q.attrs, q.los, q.his)),
                    )
                )
            out[pos] = stats

    def _exec_join(self, q: Query):
        """HIGH-S equi-join: count pairs between the outer matches and
        the inner table on join_attr == join_inner_attr.  Cost model:
        index-nested-loop when an index exists on the inner join
        attribute, hash join (one inner pass) otherwise."""
        inner_t = self.tables[q.join_table]
        outer_t = self.tables[q.table]
        ts = int(self.clock_ms) + 1
        # exact pair count (host-side sorted merge; correctness path).
        # The outer rows are the scan's: predicate and visibility at its
        # snapshot, which the stitch counts exactly once.
        snap = self.clock_ms_i32()

        def parts(t):
            return t.shards if isinstance(t, ShardedTable) else (t,)

        def flat(planes):
            return np.concatenate([np.asarray(p).reshape(-1) for p in planes])

        outer_vals = np.concatenate(
            [
                np.asarray(t.data[:, :, q.join_attr])[
                    np.asarray(
                        conj_predicate_mask(t, q.attrs, q.los, q.his)
                        & visible_mask(t, snap)
                    )
                ]
                for t in parts(outer_t)
            ]
        )
        inner = parts(inner_t)
        ib = flat(t.begin_ts for t in inner)
        ie = flat(t.end_ts for t in inner)
        ivals = flat(t.data[:, :, q.join_inner_attr] for t in inner)
        ivis = (ib <= ts) & (ts < ie)
        inner_vals = np.sort(ivals[ivis])
        lo = np.searchsorted(inner_vals, outer_vals, side="left")
        hi = np.searchsorted(inner_vals, outer_vals, side="right")
        pairs = int((hi - lo).sum())

        n_outer = int(outer_vals.shape[0])
        n_inner = int(inner_t.n_rows)
        inner_idx = None
        for bi in self.indexes_on(q.join_table):
            if (
                bi.desc.key_attrs
                and bi.desc.key_attrs[0] == q.join_inner_attr
                and bi.scheme in ("vap", "full")
            ):
                inner_idx = bi
                break
        if inner_idx is not None:
            frac = inner_idx.built_fraction(inner_t)
            probes = n_outer * (np.log2(max(n_inner, 2)) * cm.INDEX_PROBE_COST)
            cost = probes + (1.0 - frac) * n_inner
            inner_idx.last_used_ms = self.clock_ms
            return pairs, float(cost), True
        return pairs, float(n_inner), False

    def _exec_update(self, q: Query) -> ExecStats:
        t = self.tables[q.table]
        layout = self.layouts[q.table]
        if isinstance(t, ShardedTable):
            mutate = sharded_update_rows
        else:
            mutate, t = update_rows, self._writable(q.table)
        # The cost is charged from the table before the write.  A plain
        # table's planes are donated to the write, so keep what the
        # charge reads: ``n_rows`` (never donated) and the page size.
        before = _Extent(t.n_rows, t.page_size)
        with span("repro.executor.dispatch", path="update", queries=1):
            los = jnp.asarray(q.los, jnp.int32)
            his = jnp.asarray(q.his, jnp.int32)
            new_t, n_upd = mutate(
                t,
                tuple(q.attrs),
                los,
                his,
                tuple(q.set_attrs),
                jnp.asarray(q.set_vals, jnp.int32),
                self.clock_ms_i32(),
                max_new=self.update_cap,
            )
        self._store(q.table, new_t)
        n_upd = int(host_read(n_upd, "executor.update.rows"))
        # Row lookup: table scan unless an index matches the predicate.
        bi = self._choose_index(q)
        if bi is not None and bi.scheme in ("vap",):
            frac = bi.built_fraction(before)
            n_rows = int(host_read(before.n_rows, "executor.update.n_rows"))
            lookup = (1.0 - frac) * float(n_rows) + cm.INDEX_PROBE_COST * n_upd
            bi.last_used_ms = self.clock_ms
        else:
            width = scan_width_factor(layout, tuple(q.attrs), 0)
            n_rows = int(host_read(before.n_rows, "executor.update.n_rows"))
            lookup = float(n_rows) * (width / layout.n_attrs)
        maint = cm.tau_maintenance(n_upd) * max(
            len(self.indexes_on(q.table)), 0
        )
        cost = lookup + maint + float(n_upd)
        self._after_mutation(q.table)
        return ExecStats(
            cost_units=cost,
            latency_ms=cost * self.time_per_unit_ms,
            used_index=bi is not None,
            rows_modified=n_upd,
        )

    def _exec_insert(self, q: Query) -> ExecStats:
        t = self.tables[q.table]
        rows = np.asarray(q.rows, np.int32)
        if isinstance(t, ShardedTable):
            mutate = sharded_insert_rows
        else:
            mutate, t = insert_rows, self._writable(q.table)
        new_t = mutate(
            t,
            jnp.asarray(rows),
            self.clock_ms_i32(),
            rows.shape[0],
            max_new=rows.shape[0],
        )
        self._store(q.table, new_t)
        n = rows.shape[0]
        maint = cm.tau_maintenance(n) * max(len(self.indexes_on(q.table)), 0)
        cost = float(n) + maint
        self._after_mutation(q.table)
        return ExecStats(
            cost_units=cost,
            latency_ms=cost * self.time_per_unit_ms,
            used_index=False,
            rows_modified=n,
        )

    def _after_mutation(self, table: str) -> None:
        """Inserted rows are unknown to VBP covering intervals; drop
        coverage claims (entries stay; scans re-check visibility).
        Zone maps summarise page contents, so they re-derive too."""
        for key in [k for k in self._zone_maps if k[0] == table]:
            del self._zone_maps[key]
        for bi in self.indexes_on(table):
            if bi.scheme == "vbp":
                bi.vbp = vbp_invalidate_coverage(bi.vbp)
                bi.cov_union.clear()

    # ------------------------------------------------------------------
    # Tuner-side physical work, charged by the caller
    # ------------------------------------------------------------------
    def vap_build_step(
        self,
        bi: BuiltIndex,
        pages: int,
        shard: Optional[int] = None,
        page_list=None,
    ) -> float:
        """Advance a VAP/FULL index by one resumable build quantum of
        ``pages`` pages (``index.advance_build``); returns work units.
        On sharded storage the budget round-robins across shards in
        global page order (index.sharded_build_pages_vap) -- unless
        ``shard`` targets one shard's local prefix (shard-aware
        tuning), which relaxes the global prefix invariant and flips
        the index's hybrid scans to the per-shard stitch.

        Bitmap-mode indexes (``bi.coverage`` attached) route every
        quantum through ``_coverage_build_step`` instead: explicit
        ``page_list`` quanta (hot-range-first scheduling) or the
        lowest uncovered pages, order-free.  ``page_list`` is only
        meaningful in bitmap mode (prefix builds cannot express it).
        """
        t = self.tables[bi.desc.table]
        with span(
            "repro.build.apply", index=bi.desc.name, pages=int(pages)
        ) as sp:
            if bi.coverage is not None:
                work = self._coverage_build_step(
                    bi, t, pages, shard, page_list
                )
            else:
                work = self._prefix_build_step(bi, t, pages, shard)
            # work units are pages built x page size
            sp.set_metadata(pages_built=int(work) // t.page_size)
        return work

    def _prefix_build_step(
        self, bi: BuiltIndex, t, pages: int, shard: Optional[int]
    ) -> float:
        """Prefix-mode build quantum: advance the built watermark."""
        if shard is None:
            bi.vap, done = advance_build(bi.vap, t, bi.desc.key_attrs, pages)
            n_rows = host_read(t.n_rows, "executor.build_step.n_rows")
            full_pages = int(n_rows) // t.page_size
        else:
            bi.vap, done = advance_build_shard(
                bi.vap, t, bi.desc.key_attrs, shard, pages
            )
            self.pershard_built.add(bi.desc.name)
            full_pages = sum(shard_full_pages(t))
        built = host_read(bi.vap.built_pages, "executor.build_step.built")
        if int(built) >= full_pages:
            bi.complete = True
            bi.building = False
        return float(done * t.page_size)

    def _coverage_build_step(
        self, bi: BuiltIndex, t, pages: int, shard: Optional[int], page_list
    ) -> float:
        """Bitmap-mode build quantum.  All entry emission routes
        through ``build_page_list`` -- NEVER ``advance_build``, whose
        prefix replay would re-emit entries for pages the bitmap
        already covers (the bitmap is the dedup authority).  With no
        ``page_list`` the lowest uncovered eligible pages build first,
        which reproduces the legacy global page order exactly; a
        ``shard`` target keeps only that shard's pages (p % S)."""
        cov = bi.coverage
        eligible = eligible_global_pages(t)
        open_mask = ~cov.built[eligible]
        if page_list is not None:
            wanted = [int(p) for p in page_list]
            open_set = set(eligible[open_mask].tolist())
            take = np.asarray(
                [p for p in wanted if p in open_set][: int(pages)], np.int64
            )
        else:
            open_pages = eligible[open_mask]
            if shard is not None and isinstance(t, ShardedTable):
                open_pages = open_pages[open_pages % t.n_shards == shard]
            take = open_pages[: int(pages)]
        if take.size:
            bi.vap = build_page_list(bi.vap, t, bi.desc.key_attrs, take)
            cov.set_pages(take)
        if cov.built[eligible].all():
            bi.complete = True
            bi.building = False
        return float(take.size * t.page_size)

    def vbp_populate(self, bi: BuiltIndex, q: Query, max_add: int) -> float:
        """Populate the sub-domain touched by ``q``; returns work units
        (charged to the query by immediate-DL tuners -> latency spike).

        Cost model: population piggybacks on the triggering query's own
        table scan (so no extra scan term), but every harvested entry
        pays a sorted-structure insertion (the cracking/SMIX per-entry
        work), plus covering-metadata bookkeeping.
        """
        t = self.tables[bi.desc.table]
        max_add = min(int(max_add), t.capacity)
        entries_before = int(vbp_n_entries(bi.vbp))
        lo, hi = self.planner.vbp_bounds(bi, q)
        populate = (
            sharded_vbp_populate_subdomain
            if isinstance(bi.vbp, ShardedVbpState)
            else vbp_populate_subdomain
        )
        bi.vbp, n_added = populate(
            bi.vbp,
            t,
            bi.desc.key_attrs,
            lo,
            hi,
            self.clock_ms_i32(),
            max_add=max_add,
        )
        n_added = int(n_added)
        if n_added < max_add:  # the whole sub-domain fit -> now covered
            hlo, hhi = self.planner.vbp_host_bounds(bi, q)
            bi.cov_union.add(hlo, hhi)
        # Cracking-style cost: partitioning the still-uncracked region
        # (early cracks touch nearly the whole column; later ones are
        # cheap) plus sorted insertion per harvested entry.
        uncracked = max(int(t.n_rows) - entries_before, 0)
        return float(n_added) * 8.0 + 0.5 * float(uncracked)

    def clock_ms_i32(self):
        return jnp.asarray(min(int(self.clock_ms) + 1, 2**31 - 2), jnp.int32)
