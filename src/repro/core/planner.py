"""Query planner: access-path selection, selectivity estimation and
cost accounting -- pure Python, no array dispatch.

This is the optimizer half of the executor's planner/engine split.
The planner inspects the index catalog (``BuiltIndex`` records) and a
query's predicate and emits a ``ScanPlan``; the scan engine
(``core.engine``) turns plans into jitted dispatches -- one per table,
or one fan-out per shard on sharded storage.  Keeping the planner free
of jax calls means plan choice costs no device round-trips and the
same planner drives both storage layouts.

Access-path selection follows the paper (Section III, "Query
Optimization"): for a scan, consider each built index whose leading
key attribute is constrained by the predicate, estimate selectivity,
and pick a hybrid scan for selective queries -- falling back to a
table scan when the predicate is not selective or no index matches.
FULL-scheme indexes are usable only when complete; VBP indexes only
when the query sub-domain is covered.

``estimate_scan_cost`` is the planner's what-if export: the cost this
database would charge for a scan, in the same tuple-touch units the
engine's measured ``scan_cost`` accounting produces, without
dispatching anything.  The replica router (``core.replica``) compares
it across replicas to send each query to the cheapest physical design.
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Optional, Tuple

from repro.core import cost_model as cm
from repro.core.cost_model import IndexDescriptor
from repro.core.index import (
    ShardedIndex,
    ShardedVbpState,
    key_range,
    vbp_n_entries,
)
from repro.core.layout import LayoutState, scan_width_factor
from repro.core.table import ShardedTable
from repro.obs import host_read

HYBRID_SELECTIVITY_CUTOFF = 0.20  # optimizer switches to table scan above


class IntervalUnion:
    """Host-side merged interval set over composite keys.

    The jnp-side VbpState tracks exact-interval coverage (enough for
    the jitted kernels); real cracking additionally benefits from the
    *union* of overlapping populated sub-domains -- two overlapping
    cracks jointly cover their union.  The planner keeps this merged
    view per VBP index and uses it for access-path decisions.
    """

    def __init__(self):
        self.ivs: list = []  # sorted disjoint [(lo, hi)] of key tuples

    def add(self, lo, hi) -> None:
        ivs = self.ivs + [(lo, hi)]
        ivs.sort()
        merged = [ivs[0]]
        for a, b in ivs[1:]:
            la, lb = merged[-1]
            if a <= lb or a == lb:  # touching/overlapping (tuple compare)
                if b > lb:
                    merged[-1] = (la, b)
            else:
                merged.append((a, b))
        self.ivs = merged

    def covers(self, lo, hi) -> bool:
        for a, b in self.ivs:
            if a <= lo and hi <= b:
                return True
            if a > lo:
                break
        return False

    def clear(self) -> None:
        self.ivs = []


def built_fraction_of(scheme: str, vap, vbp, table) -> float:
    """Built fraction from raw index state (shared by the live catalog
    record and the planner's frozen snapshot view)."""
    n_rows = int(host_read(table.n_rows, "planner.built_fraction.n_rows"))
    if scheme in ("vap", "full"):
        full_pages = max(n_rows // table.page_size, 1)
        built = host_read(vap.built_pages, "planner.built_fraction.built")
        return min(int(built) / full_pages, 1.0)
    entries = host_read(vbp_n_entries(vbp), "planner.built_fraction.entries")
    return min(int(entries) / max(n_rows, 1), 1.0)


@dataclass
class BuiltIndex:
    """Catalog entry for one built (or building) index.

    ``coverage`` (a ``core.index.PageCoverage``) generalizes the VAP
    built prefix to a built-page bitmap; it is None for every legacy
    index (flag off) and only the crack-on-scan / decay machinery
    attaches one.  When present it is the coverage authority: built
    fraction and size accounting read the bitmap, the planner routes
    non-prefix shapes to the masked path, and the build path routes
    through explicit page lists (never ``advance_build``, which would
    re-index adopted pages and duplicate their entries).
    """

    desc: IndexDescriptor
    scheme: str  # 'vap' | 'vbp' | 'full'
    vap: Optional[object] = None  # AdHocIndex | ShardedIndex
    vbp: Optional[object] = None  # VbpState | ShardedVbpState
    cov_union: Optional[IntervalUnion] = None  # VBP merged coverage
    complete: bool = False  # FULL usable flag
    building: bool = True  # under construction (VAP/FULL)
    created_ms: float = 0.0
    last_used_ms: float = 0.0
    coverage: Optional[object] = None  # PageCoverage (bitmap mode)

    def built_fraction(self, table) -> float:
        if self.coverage is not None and self.scheme in ("vap", "full"):
            n_rows = host_read(table.n_rows, "planner.built_fraction.n_rows")
            full_pages = max(int(n_rows) // table.page_size, 1)
            return min(self.coverage.count() / full_pages, 1.0)
        return built_fraction_of(self.scheme, self.vap, self.vbp, table)

    def size_bytes(self) -> float:
        if self.coverage is not None and self.scheme in ("vap", "full"):
            # Coverage-aware: decay clears bits without compacting the
            # entry array, so the bitmap (not n_entries) is what the
            # memory cap governs.
            return 12.0 * float(
                self.coverage.count() * self.coverage.page_size
            )
        if self.scheme in ("vap", "full"):
            entries = self.vap.n_entries
        else:
            entries = vbp_n_entries(self.vbp)
        return 12.0 * float(int(host_read(entries, "planner.size.entries")))


@dataclass(frozen=True)
class IndexSnapshot:
    """Frozen (front-buffer) view of one BuiltIndex's usable state.

    Index states are immutable pytrees, so a snapshot is a reference
    capture: while build quanta replace ``BuiltIndex.vap`` underneath
    a running burst (the back buffer), every plan minted under the
    snapshot keeps resolving against these captured states.
    """

    vap: Optional[object]
    vbp: Optional[object]
    complete: bool


def _engine_state(path: str, vap, vbp):
    """Raw sorted-entry state for the engine given an access path.

    For the pure-VBP path over sharded storage the per-shard entry
    arrays are re-wrapped as a ShardedIndex: the engine's pure index
    scan only needs the entry shards, not the covering metadata.
    """
    if path == "pure_vbp":
        if isinstance(vbp, ShardedVbpState):
            return ShardedIndex(vbp.shards)
        return vbp.index
    return vap


@dataclass(frozen=True)
class ScanPlan:
    """One planned scan: the access path plus the index serving it.

    ``path`` is 'table' | 'hybrid' | 'hybrid_ps' | 'hybrid_masked' |
    'pure_vbp' | 'pure_vap'.  The engine receives the raw index state
    via ``index_state`` so it never touches catalog records.
    ``pinned_state`` is the index state the plan was minted against --
    the planner pins it at plan time so an in-flight burst keeps a
    stable view while build quanta advance the live catalog
    underneath (double buffering); plans constructed by hand without a
    pin fall back to the live record.  ``pinned_coverage`` is the
    frozen ``CoverageView`` for the masked path, pinned under the same
    rule (all burst plans are minted before any dispatch or drain, so
    the live bitmap reads at plan time are burst-consistent even
    though crack adoption mutates it during replay).
    """

    path: str
    index: Optional[BuiltIndex] = None
    pinned_state: Optional[object] = None
    pinned_coverage: Optional[object] = None

    @property
    def key_attrs(self) -> Tuple[int, ...]:
        return self.index.desc.key_attrs if self.index is not None else ()

    @property
    def index_state(self):
        """Raw sorted-entry state for the engine (None for table scans)."""
        bi = self.index
        if bi is None:
            return None
        if self.pinned_state is not None:
            return self.pinned_state
        return _engine_state(self.path, bi.vap, bi.vbp)

    @property
    def group_key(self):
        """Batch-compatibility key fragment (path + serving index)."""
        return (self.path, self.index.desc.name if self.index else None)


class QueryPlanner:
    """Access-path planner over a Database's catalog.

    Holds only references to live catalog state (tables + indexes), so
    plans always reflect the current configuration; all methods are
    host-side Python.  The database is held weakly: a database and its
    planner form no reference cycle, so a dropped database frees its
    tables at once, not at the next cyclic garbage collection.
    """

    def __init__(self, db):
        self._db = weakref.ref(db)
        self._snap: Optional[dict] = None  # name -> IndexSnapshot

    @property
    def db(self):
        return self._db()

    # -- catalog double buffering ----------------------------------------
    def begin_snapshot(self) -> None:
        """Freeze the catalog front buffer: every plan minted until
        ``end_snapshot`` resolves index state, built fraction and
        completeness against the states captured here, while build
        quanta keep advancing the live (back-buffer) records."""
        self._snap = {
            name: IndexSnapshot(bi.vap, bi.vbp, bi.complete)
            for name, bi in self.db.indexes.items()
        }

    def end_snapshot(self) -> None:
        """Swap the buffers: the next burst plans against whatever the
        drained quanta built."""
        self._snap = None

    def _states(self, bi: BuiltIndex):
        """(vap, vbp, complete) from the active snapshot, else live."""
        if self._snap is not None:
            snap = self._snap.get(bi.desc.name)
            if snap is not None:
                return snap.vap, snap.vbp, snap.complete
        return bi.vap, bi.vbp, bi.complete

    # -- selectivity -----------------------------------------------------
    @staticmethod
    def estimate_selectivity(q) -> float:
        """Cheap uniform-assumption estimate from predicate ranges over
        the TUNER attribute domain [1, 1m]; used only for plan choice
        (measured selectivity feeds the monitor afterwards)."""
        sel = 1.0
        for lo, hi in zip(q.los, q.his):
            width = max(float(hi) - float(lo) + 1.0, 0.0)
            sel *= min(width / 1_000_000.0, 1.0)
        return sel

    # -- index choice ----------------------------------------------------
    def choose_index(self, q) -> Optional[BuiltIndex]:
        best, best_key = None, (-1, -1.0)
        for bi in self.db.indexes.values():
            if not cm.index_matches(bi.desc, q.table, q.attrs):
                continue
            vap, vbp, complete = self._states(bi)
            if bi.scheme == "full" and not complete:
                continue
            covered = len(set(bi.desc.key_attrs) & set(q.attrs))
            frac = built_fraction_of(
                bi.scheme, vap, vbp, self.db.tables[q.table]
            )
            if bi.scheme == "vbp":
                lo, hi = self.vbp_host_bounds(bi, q)
                if not bi.cov_union.covers(lo, hi):
                    continue
            key = (covered, frac)
            if key > best_key:
                best, best_key = bi, key
        return best

    def plan_scan(self, q) -> ScanPlan:
        bi = None
        if self.estimate_selectivity(q) <= HYBRID_SELECTIVITY_CUTOFF:
            bi = self.choose_index(q)
        if bi is None:
            return ScanPlan("table")
        vap, vbp, complete = self._states(bi)
        if bi.scheme == "vbp":
            return ScanPlan(
                "pure_vbp",
                bi,
                pinned_state=_engine_state("pure_vbp", vap, vbp),
            )
        if bi.scheme == "full" and complete:
            return ScanPlan("pure_vap", bi, pinned_state=vap)
        cov = bi.coverage
        if cov is not None and not self._coverage_is_legacy(cov, vap):
            return ScanPlan(
                "hybrid_masked",
                bi,
                pinned_state=vap,
                pinned_coverage=self._pin_coverage(bi, cov),
            )
        path = "hybrid"  # VAP (or FULL still building)
        if self._needs_pershard_stitch(bi, vap):
            path = "hybrid_ps"
        return ScanPlan(path, bi, pinned_state=vap)

    # -- what-if cost (replica routing) ----------------------------------
    def estimate_scan_cost(self, q) -> float:
        """What-if cost of serving ``q`` under the CURRENT catalog, in
        the engine's tuple-touch units -- ``scan_cost`` arithmetic fed
        with estimated (not measured) pages and probes.  Pure host-side
        and side-effect free: no dispatch, no ``last_used_ms`` touch,
        no monitor observation.  The replica router compares this
        number across replicas (``core.replica.ReplicaSet``), so it
        must be deterministic for a given catalog state -- it reads
        only the catalog and the query, never wall time or hashes.
        """
        t = self.db.tables[q.table]
        layout = self.db.layouts[q.table]
        psz = t.page_size
        n_rows = int(host_read(t.n_rows, "planner.estimate.n_rows"))
        if isinstance(t, ShardedTable):
            used_pages = sum(
                -(-int(host_read(s.n_rows, "planner.estimate.n_rows")) // psz)
                for s in t.shards
            )
        else:
            used_pages = -(-n_rows // psz)
        plan = self.plan_scan(q)
        sel = self.estimate_selectivity(q)
        if plan.path == "table":
            cost = scan_cost(layout, q.accessed_attrs, psz, used_pages, 0.0, 0)
        elif plan.path in ("pure_vbp", "pure_vap"):
            cost = scan_cost(
                layout, q.accessed_attrs, psz, 0, sel * n_rows, t.n_pages
            )
        else:  # hybrid flavours: indexed prefix probes + table suffix
            frac = plan.index.built_fraction(t)
            start = int(frac * used_pages)
            cost = scan_cost(
                layout,
                q.accessed_attrs,
                psz,
                used_pages - start,
                sel * frac * n_rows,
                start,
            )
        if q.join_table is not None:
            inner = self.db.tables[q.join_table]
            n_inner = int(inner.n_rows)
            has_idx = any(
                bi.scheme in ("vap", "full")
                and not bi.building
                and cm.index_matches(
                    bi.desc, q.join_table, (q.join_inner_attr,)
                )
                for bi in self.db.indexes.values()
            )
            cost += (
                n_inner * cm.INDEX_PROBE_COST if has_idx else float(n_inner)
            )
        return cost

    @staticmethod
    def _coverage_is_legacy(cov, vap) -> bool:
        """A bitmap that IS the prefix the index watermark claims (and
        has no stray entries beyond it) takes the legacy start_page
        paths bit for bit -- routing is a fast-path choice only."""
        site = "planner.coverage_is_legacy.built"
        if isinstance(vap, ShardedIndex):
            built = sum(
                int(host_read(ix.built_pages, site)) for ix in vap.shards
            )
        else:
            built = int(host_read(vap.built_pages, site))
        return cov.legacy_prefix_ok(built)

    def _pin_coverage(self, bi: BuiltIndex, cov):
        """Freeze the live bitmap into the view the burst pins."""
        t = self.db.tables[bi.desc.table]
        if isinstance(t, ShardedTable):
            return cov.view(t.n_shards, max(x.n_pages for x in t.shards))
        return cov.view(1, t.n_pages)

    def _needs_pershard_stitch(self, bi: BuiltIndex, vap) -> bool:
        """The global hybrid stitch is sound only while the shard-local
        built prefixes partition one global page prefix under the
        round-robin page map.  Shard-targeted build quanta (shard-aware
        tuning) and adopted non-round-robin shard layouts both break
        that, so those scans stitch per shard instead."""
        if not isinstance(vap, ShardedIndex):
            return False
        if bi.desc.name in getattr(self.db, "pershard_built", ()):
            return True
        t = self.db.tables.get(bi.desc.table)
        return isinstance(t, ShardedTable) and not self.db.table_is_round_robin(
            bi.desc.table
        )

    # -- VBP key bounds --------------------------------------------------
    @staticmethod
    def vbp_host_key_bounds(bi: BuiltIndex, q):
        """Host-side composite-key bounds ((hi,lo) int tuples)."""
        pmap = {a: k for k, a in enumerate(q.attrs)}
        ka = bi.desc.key_attrs
        lo0, hi0 = int(q.los[pmap[ka[0]]]), int(q.his[pmap[ka[0]]])
        if len(ka) == 2 and ka[1] in pmap:
            lo1, hi1 = int(q.los[pmap[ka[1]]]), int(q.his[pmap[ka[1]]])
        elif len(ka) == 2:
            lo1, hi1 = -(2**31) + 1, 2**31 - 2
        else:
            lo1, hi1 = 0, 0
        return (lo0, lo1), (hi0, hi1)

    @classmethod
    def vbp_host_bounds(cls, bi: BuiltIndex, q):
        return cls.vbp_host_key_bounds(bi, q)

    @classmethod
    def vbp_bounds(cls, bi: BuiltIndex, q):
        (lo0, lo1), (hi0, hi1) = cls.vbp_host_key_bounds(bi, q)
        if len(bi.desc.key_attrs) == 2:
            return key_range(lo0, hi0, lo1, hi1)
        return key_range(lo0, hi0)


def scan_cost(
    layout: LayoutState,
    accessed_attrs,
    page_size: int,
    pages_scanned: int,
    entries_probed: float,
    start_page: int,
) -> float:
    """Tuple-touch cost of one executed scan.

    Table-scan units scale with the layout's effective width
    (width/n_attrs == 1 for untuned NSM pages); index probes are
    narrow and layout-independent.
    """
    width = scan_width_factor(layout, accessed_attrs, from_page=start_page)
    cost = float(pages_scanned) * page_size * (width / layout.n_attrs)
    return cost + float(entries_probed) * cm.INDEX_PROBE_COST
