"""Plain numpy reference: replays statements one by one on the host.

A copy of ``NumpyReplay`` from ``chip_smoke.py``, independent of the
engine.  Statement ``i`` (in service order) runs at snapshot
``ts = i + 1``; loaded rows begin at 0 and never end.  The engine's
snapshots follow a clock that never goes back and a burst holds only
scans, so the same order of statements sees the same versions.

* A scan returns ``(SUM(agg_attr), COUNT(*), 0)`` over the rows whose
  predicate holds and whose version is visible
  (``begin_ts <= ts < end_ts``), the sum wrapped to int32 as the
  engine documents.
* An UPDATE terminates the first ``update_cap`` visible matches in rid
  order and appends their new versions at the append watermark, with
  the SET attributes changed (``core.table.update_rows``); it returns
  ``(0, 0, rows_modified)``.

Only the columns the statements read are kept: the predicate and
aggregate attributes.  A SET attribute outside them changes nothing a
later statement reads.  Where no statement writes, scans are answered
from one sort of the predicate column with prefix sums of the
aggregate column, which gives the same answers as the masks.

``sum_dtype=np.float32`` makes the control: the same replay with every
SUM accumulated in float32, the approximate aggregate that a faster
accumulation would give.  It breaks the configuration's guarantee of
exact sums, and the comparison has to find it.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

NEVER_TS = 2**31 - 1


def _wrap32(total: int) -> int:
    return int(np.array(total, np.int64).astype(np.int32))


def columns_read(queries: Iterable) -> List[int]:
    """Attributes whose values decide some statement's answer."""
    out = set()
    for q in queries:
        out.update(int(a) for a in q.attrs)
        if q.kind == "scan":
            out.add(int(q.agg_attr))
    return sorted(out)


class NumpyReplay:
    """Host-side MVCC column store over the columns ``planes`` holds."""

    def __init__(self, planes: Dict[int, np.ndarray], n_rows: int,
                 update_cap: int, read_only: bool = False,
                 sum_dtype=np.int64):
        self.cols = {a: np.array(v, np.int32) for a, v in planes.items()}
        cap = len(next(iter(self.cols.values())))
        self.capacity = cap
        self.n_rows = int(n_rows)
        self.begin = np.full(cap, NEVER_TS, np.int32)
        self.begin[: self.n_rows] = 0
        self.end = np.full(cap, NEVER_TS, np.int32)
        self.update_cap = int(update_cap)
        self.read_only = read_only
        self.sum_dtype = sum_dtype
        self._sorted: Dict[Tuple[int, int], tuple] = {}

    def _match(self, q, ts: int) -> np.ndarray:
        """Visible matches among the slots below the append watermark
        (every slot above it is empty)."""
        n = self.n_rows
        mask = (self.begin[:n] <= ts) & (ts < self.end[:n])
        for a, lo, hi in zip(q.attrs, q.los, q.his):
            col = self.cols[a][:n]
            mask &= (col >= lo) & (col <= hi)
        return mask

    def _scan_sorted(self, q) -> Tuple[int, int, int]:
        key = (int(q.attrs[0]), int(q.agg_attr))
        got = self._sorted.get(key)
        if got is None:
            n = self.n_rows
            order = np.argsort(self.cols[key[0]][:n], kind="stable")
            vals = self.cols[key[0]][:n][order].astype(np.int64)
            cs = np.concatenate([[0], np.cumsum(
                self.cols[key[1]][:n][order], dtype=self.sum_dtype)])
            got = self._sorted[key] = (vals, cs)
        vals, cs = got
        i0 = int(np.searchsorted(vals, q.los[0], side="left"))
        i1 = int(np.searchsorted(vals, q.his[0], side="right"))
        if i1 <= i0:
            return (0, 0, 0)
        return (_wrap32(int(cs[i1] - cs[i0])), i1 - i0, 0)

    def run(self, stmt: int, q) -> Tuple[int, int, int]:
        """(agg_sum, count, rows_modified) of statement ``stmt``."""
        ts = stmt + 1
        if q.kind == "scan" and self.read_only and len(q.attrs) == 1:
            return self._scan_sorted(q)
        if q.kind not in ("scan", "update"):
            raise ValueError(f"the replay has no {q.kind!r} statement")
        if q.kind == "update" and self.read_only:
            raise ValueError("an update in a read-only replay")
        mask = self._match(q, ts)
        if q.kind == "scan":
            vals = self.cols[q.agg_attr][: self.n_rows][mask]
            total = int(vals.sum(dtype=self.sum_dtype))
            return (_wrap32(total), int(mask.sum()), 0)
        rids = np.flatnonzero(mask)[: self.update_cap]
        n = len(rids)
        self.end[rids] = ts
        new = np.arange(self.n_rows, self.n_rows + n)
        keep = new < self.capacity
        sets = dict(zip(q.set_attrs, q.set_vals))
        for a, col in self.cols.items():
            vals = col[rids[keep]].copy()
            if a in sets:
                vals[:] = sets[a]
            col[new[keep]] = vals
        self.begin[new[keep]] = ts
        self.end[new[keep]] = NEVER_TS
        self.n_rows = min(self.n_rows + n, self.capacity)
        return (0, 0, n)

    def run_all(self, queries) -> List[Tuple[int, int, int]]:
        return [self.run(i, q) for i, q in enumerate(queries)]
