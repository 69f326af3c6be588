"""The MVCC write path writes in place under explicit ownership.

Contracts under test (core/table.py's ownership rule and
``Database._writable`` in core/executor.py):

* A database never writes a table it was given: loaded arrays stay
  alive and unchanged after UPDATEs and INSERTs, by ``execute`` and
  inside mixed ``execute_batch`` bursts, and a second database over
  them sees the loaded state.
* It copies a table it does not own once (one ``repro.table.own`` span
  per table per database), then writes its own copy in place: the
  planes a write consumed are deleted.
* Results equal a plain numpy MVCC reference on a wide-shaped table
  under a 50/50 scan/update stream with the tuner running, and costs
  and the simulated clock equal those of a database that copies before
  every write.
* Replicas that start from shared tables, and a replica that rejoins
  after an outage, stay bit-identical when every replica writes.
"""

import numpy as np
import pytest

from repro.api import (Database, FaultInjector, FaultSchedule,
                       PredictiveTuner, QueryGen, ReplicaOutage, ReplicaSet,
                       TunerConfig, make_tuner_db)
from repro.core import executor
from repro.core.cost_model import index_size_bytes

N_ROWS = 3_000
NEVER = 2**31 - 1


@pytest.fixture(scope="module")
def dbt():
    return make_tuner_db(n_rows=N_ROWS, include_wide=True)


def snapshot(tables):
    return {name: tuple(np.array(x) for x in t) for name, t in tables.items()}


def same(a, b):
    return a.keys() == b.keys() and all(
        all(np.array_equal(x, y) for x, y in zip(a[k], b[k])) for k in a)


def writes(gen, table="narrow", n=6):
    """Scans, updates and inserts on one table, in a fixed order."""
    gen.table = table
    out = []
    for i in range(n):
        out += [gen.low_s(attr=1 + i % 3), gen.low_u(attr=1 + i % 3),
                gen.low_s(attr=2), gen.high_u(attrs=(1, 2), sel=0.02)]
        if i % 3 == 2:
            out.append(gen.ins(n=8))
    return out


def run(db, queries, batched):
    if batched:
        return db.execute_batch(queries)
    return [db.execute(q) for q in queries]


def result(st):
    return (st.agg_sum, st.count, st.rows_modified)


@pytest.mark.parametrize("batched", [False, True], ids=["execute", "batch"])
@pytest.mark.parametrize("table", ["narrow", "wide"])
def test_loaded_tables_are_never_written(dbt, table, batched):
    loaded = snapshot(dbt.tables)
    queries = writes(QueryGen(dbt, seed=3), table)
    db = Database(dict(dbt.tables))
    got = run(db, queries, batched)
    assert sum(st.rows_modified for st in got) > 0
    assert db.tables[table] is not dbt.tables[table]
    for t in dbt.tables.values():
        assert not any(x.is_deleted() for x in t)
    assert same(snapshot(dbt.tables), loaded)
    # A second database over the loaded tables sees the loaded state,
    # and serves the same stream to the same answers.
    db2 = Database(dict(dbt.tables))
    assert same(snapshot(db2.tables), loaded)
    again = run(db2, queries, not batched)
    assert [result(s) for s in again] == [result(s) for s in got]


@pytest.mark.parametrize("kind", ["update", "insert"])
def test_a_later_write_consumes_the_planes_of_the_last(dbt, kind):
    gen = QueryGen(dbt, seed=5)
    write = (lambda: gen.low_u(attr=1)) if kind == "update" else gen.ins
    db = Database(dict(dbt.tables))
    db.execute(write())
    first = db.tables["narrow"]
    db.execute(write())
    assert all(x.is_deleted() for x in first[:3])
    assert not first.n_rows.is_deleted()  # never donated
    assert not any(x.is_deleted() for x in db.tables["narrow"])


def test_one_copy_per_table_per_database(dbt, monkeypatch):
    copies = []
    span = executor.span

    def recording(name, **counts):
        if name == "repro.table.own":
            copies.append((counts["table"], counts["bytes"]))
        return span(name, **counts)

    monkeypatch.setattr(executor, "span", recording)
    gen = QueryGen(dbt, seed=7)
    queries = writes(gen, "narrow", 3) + writes(gen, "wide", 3)
    dbs = [Database(dict(dbt.tables)) for _ in range(2)]
    for db in dbs:
        run(db, queries, batched=True)
    t = dbt.tables
    want = [("narrow", (t["narrow"].data.size + 2 * t["narrow"].end_ts.size)
             * 4), ("wide", (t["wide"].data.size + 2 * t["wide"].end_ts.size)
                    * 4)]
    assert copies == want * 2
    # A table handed out is no longer owned: the next write copies.
    shared = dbs[0].share_tables()
    dbs[0].execute(gen.low_u(attr=1))
    assert copies[-1][0] == "wide" and len(copies) == 5
    assert not any(x.is_deleted() for x in shared["wide"])


class NumpyMVCC:
    """Plain MVCC store on the host: statement i runs at snapshot i + 1."""

    def __init__(self, table):
        cap = table.capacity
        self.data = np.array(table.data).reshape(cap, -1)
        self.begin = np.array(table.begin_ts).reshape(cap)
        self.end = np.array(table.end_ts).reshape(cap)
        self.n = int(table.n_rows)
        self.cap = cap

    def run(self, ts, q, update_cap=512):
        n = self.n
        mask = (self.begin[:n] <= ts) & (ts < self.end[:n])
        for a, lo, hi in zip(q.attrs, q.los, q.his):
            mask &= (self.data[:n, a] >= lo) & (self.data[:n, a] <= hi)
        if q.kind == "scan":
            s = self.data[:n, q.agg_attr][mask].astype(np.int64).sum()
            return (int(np.array(s).astype(np.int32)), int(mask.sum()), 0)
        rids = np.flatnonzero(mask)[:update_cap]
        self.end[rids] = ts
        rows = self.data[rids].copy()
        rows[:, list(q.set_attrs)] = q.set_vals
        new = np.arange(n, n + len(rids))
        keep = new < self.cap
        self.data[new[keep]] = rows[keep]
        self.begin[new[keep]] = ts
        self.end[new[keep]] = NEVER
        self.n = min(n + len(rids), self.cap)
        return (0, 0, len(rids))


class CopyingDatabase(Database):
    """Copies a table before every write, as every write did before
    writes went in place."""

    def _writable(self, name):
        self._owned.pop(name, None)
        return super()._writable(name)


def balanced_stream(dbt, n=120, seed=13):
    """The paper's balanced mix on the wide table: half LOW-S, half
    LOW-U / HIGH-U, in phases of 20 over three attributes."""
    gen = QueryGen(dbt, table="wide", seed=seed, selectivity=0.05)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        a = (7, 19, 101)[(i // 20) % 3]
        u = rng.uniform()
        if u < 0.5:
            out.append(gen.low_s(attr=a))
        elif u < 0.75:
            out.append(gen.low_u(attr=a, sel=0.01))
        else:
            out.append(gen.high_u(attrs=(a, 19 if a != 19 else 7), sel=0.05))
    return out


def serve(db_cls, dbt, queries):
    db = db_cls(dict(dbt.tables))
    tuner = PredictiveTuner(db, TunerConfig(
        storage_budget_bytes=index_size_bytes(N_ROWS) * 4,
        candidate_min_count=2))
    stats, clocks = [], []
    for i in range(0, len(queries), 8):
        burst = queries[i:i + 8]
        for q, st in zip(burst, db.execute_batch(burst)):
            tuner.on_query(q, st)
            stats.append(st)
        tuner.tuning_cycle(idle=False)
        clocks.append(db.clock_ms)
    return db, stats, clocks


def test_balanced_wide_stream_matches_reference_and_copying_path(dbt):
    queries = balanced_stream(dbt)
    db, stats, clocks = serve(Database, dbt, queries)
    assert db.indexes  # the tuner built while the stream wrote
    ref = NumpyMVCC(dbt.tables["wide"])
    want = [ref.run(i + 1, q) for i, q in enumerate(queries)]
    assert [result(s) for s in stats] == want
    assert sum(w[2] for w in want) > 0
    _, copying, copy_clocks = serve(CopyingDatabase, dbt, queries)
    assert [(s.cost_units, s.latency_ms, s.used_index, result(s))
            for s in stats] == [
        (s.cost_units, s.latency_ms, s.used_index, result(s))
        for s in copying]
    assert clocks == copy_clocks


def test_replicas_sharing_tables_stay_identical(dbt):
    loaded = snapshot(dbt.tables)
    queries = writes(QueryGen(dbt, seed=17), "narrow", 4)
    single = Database(dict(dbt.tables))
    want = [result(st) for st in run(single, queries, batched=True)]
    rs = ReplicaSet(Database(dict(dbt.tables)), 3)
    got = [result(st) for st in rs.execute_batch(queries)]
    assert got == want
    for d in rs.dbs:
        assert same(snapshot(d.tables), snapshot(single.tables))
    assert same(snapshot(dbt.tables), loaded)


def test_rejoined_replica_writes_in_place_and_stays_identical(dbt):
    gen = QueryGen(dbt, seed=19)

    def stmt(i):
        return gen.low_u() if i % 3 == 2 else gen.low_s(attr=1 + (i % 2))

    db = Database(dict(dbt.tables))
    db.execute(gen.low_u())  # owns its copy before the set shares it
    rs = ReplicaSet(db, 3)
    for i in range(4):
        rs.execute(stmt(i))
    lat = rs.execute(gen.low_s(attr=1)).latency_ms
    down, up = rs.clock_ms + 0.25 * lat, rs.clock_ms + 6.0 * lat
    rs.fault_injector = FaultInjector(
        FaultSchedule(outages=(ReplicaOutage(1, down, up),)), recovery=True)
    i = 5
    while rs.clock_ms <= up + lat:
        rs.execute(stmt(i))
        i += 1
    rs.execute(gen.low_u())  # polls: the replica is back, and writes
    assert rs.rejoins == 1 and rs.downtime_ms[1] > 0.0
    for d in rs.dbs[1:]:
        assert same(snapshot(d.tables), snapshot(rs.dbs[0].tables))
        assert d._owned["narrow"] is d.tables["narrow"]
    assert not any(x.is_deleted() for x in dbt.tables["narrow"])
