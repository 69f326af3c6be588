"""Shard-invariance tests for the planner/engine split.

Contract: for any shard count, ``Database`` produces bit-identical
query results AND bit-identical cost/clock/monitor accounting to the
single-shard engine -- across table / hybrid / pure-index access
paths, across mutations, and across full workload runs with a live
tuner.  Storage-level equivalence (sharded mutators vs the single
table ops, global-page-order VAP builds) is asserted directly against
the unsharded oracle.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.bench_db import QueryGen, make_tuner_db
from repro.bench_db.runner import RunConfig, RunResult, run_workload
from repro.bench_db.workloads import Workload, hybrid_workload
from repro.core import Database, IndexDescriptor, make_dl_tuner
from repro.core.baselines import DisabledTuner
from repro.core.index import make_sharded_index, sharded_build_pages_vap
from repro.core.index import build_pages_vap, make_index
from repro.core.table import (ShardedTable, load_table, shard_table,
                              unshard_table)

SRC = make_tuner_db(n_rows=3_000, page_size=128)
N_PAGES = SRC.tables["narrow"].n_pages


def _stats_key(s):
    return (s.agg_sum, s.count, s.cost_units, s.latency_ms, s.used_index,
            s.rows_modified)


def _mk_db(num_shards, scheme=None, build_pages=0):
    db = Database(dict(SRC.tables), num_shards=num_shards)
    if scheme is not None:
        bi = db.create_index(IndexDescriptor("narrow", (1,)), scheme)
        if build_pages:
            db.vap_build_step(bi, pages=build_pages)
    return db


def _assert_invariant(mk, queries, shard_counts=(2, 4)):
    """Same queries through 1-shard execute loop and N-shard batch."""
    ref_db = mk(1)
    ref = [ref_db.execute(q) for q in queries]
    for S in shard_counts:
        db = mk(S)
        got = db.execute_batch(queries)
        for i, (a, b) in enumerate(zip(ref, got)):
            assert _stats_key(a) == _stats_key(b), \
                (S, i, queries[i].template, a, b)
        assert db.clock_ms == pytest.approx(ref_db.clock_ms, abs=1e-9)
        assert list(db.monitor.records) == list(ref_db.monitor.records)
        for name, t in db.tables.items():
            if isinstance(t, ShardedTable):
                t = unshard_table(t)
            r = ref_db.tables[name]
            np.testing.assert_array_equal(np.asarray(t.data),
                                          np.asarray(r.data))
            np.testing.assert_array_equal(np.asarray(t.begin_ts),
                                          np.asarray(r.begin_ts))
            np.testing.assert_array_equal(np.asarray(t.end_ts),
                                          np.asarray(r.end_ts))
            assert int(t.n_rows) == int(r.n_rows)
    return ref_db


# ---------------------------------------------------------------------------
# Storage level: partition round-trip and global-page-order builds
# ---------------------------------------------------------------------------

def test_shard_table_roundtrip_ragged():
    """25 pages over 2/3/4 shards (unequal local page counts) survives
    a shard/unshard round trip; local watermarks sum to the global."""
    rng = np.random.default_rng(0)
    t = load_table(rng.integers(0, 100, size=(300, 4)).astype(np.int32),
                   page_size=16, n_pages=25)
    for S in (1, 2, 3, 4):
        stt = shard_table(t, S)
        assert stt.n_pages == 25 and stt.page_size == 16
        assert sum(int(x.n_rows) for x in stt.shards) == int(t.n_rows)
        back = unshard_table(stt)
        np.testing.assert_array_equal(np.asarray(back.data),
                                      np.asarray(t.data))
        np.testing.assert_array_equal(np.asarray(back.begin_ts),
                                      np.asarray(t.begin_ts))
        assert int(back.n_rows) == int(t.n_rows)


def test_database_adopts_presharded_tables():
    """Handing pre-sharded tables to Database keeps the shard layout
    (no silent unshard); an explicit num_shards still wins."""
    tables = {name: shard_table(t, 4) for name, t in SRC.tables.items()}
    db = Database(dict(tables))
    assert db.num_shards == 4
    assert all(isinstance(t, ShardedTable) and t.n_shards == 4
               for t in db.tables.values())
    db2 = Database(dict(tables), num_shards=2)
    assert db2.num_shards == 2
    assert all(t.n_shards == 2 for t in db2.tables.values())


def test_sharded_vap_build_is_global_prefix():
    """Stepped budgets: per-shard built prefixes always partition the
    global prefix, and the entry multiset matches the 1-shard build."""
    t = SRC.tables["narrow"]
    for S in (2, 4):
        stt = shard_table(t, S)
        sx = make_sharded_index(stt)
        ix = make_index(t.capacity)
        for budget in (3, 5, 1, 7):
            ix = build_pages_vap(ix, t, (1,), pages_per_cycle=budget)
            sx = sharded_build_pages_vap(sx, stt, (1,),
                                         pages_per_cycle=budget)
            m = int(ix.built_pages)
            assert int(sx.built_pages) == m
            assert int(sx.n_entries) == int(ix.n_entries)
            for s, shard_ix in enumerate(sx.shards):
                # shard s owns global pages s, s+S, ...: its local
                # prefix must cover exactly those below the global one
                assert int(shard_ix.built_pages) == \
                    max(0, -(-(m - s) // S))


# ---------------------------------------------------------------------------
# Access paths: 1 vs N shards bit-identical
# ---------------------------------------------------------------------------

def test_shard_invariance_table_scan_path():
    gen = QueryGen(SRC, selectivity=0.01, seed=3)
    queries = [gen.low_s(attr=1) if i % 3 else gen.mod_s()
               for i in range(16)]
    _assert_invariant(lambda S: _mk_db(S), queries)


def test_shard_invariance_hybrid_path():
    gen = QueryGen(SRC, selectivity=0.01, seed=5)
    queries = [gen.low_s(attr=1) for _ in range(12)]
    db = _assert_invariant(
        lambda S: _mk_db(S, "vap", build_pages=N_PAGES // 3), queries)
    assert any(r.used_index for r in [db.execute(q, observe=False)
                                      for q in queries[:3]])


def test_shard_invariance_pure_index_path():
    gen = QueryGen(SRC, selectivity=0.01, seed=7)
    queries = [gen.low_s(attr=1) for _ in range(8)]
    db = _assert_invariant(
        lambda S: _mk_db(S, "full", build_pages=N_PAGES), queries)
    assert db.execute(queries[0], observe=False).used_index


def test_shard_invariance_vbp_covered():
    gen = QueryGen(SRC, selectivity=0.01, seed=11)
    queries = [gen.low_s(attr=1, pos=0.3) for _ in range(8)]

    def mk(S):
        db = Database(dict(SRC.tables), num_shards=S)
        bi = db.create_index(IndexDescriptor("narrow", (1,)), "vbp")
        db.vbp_populate(bi, queries[0],
                        max_add=SRC.tables["narrow"].capacity)
        return db

    db = _assert_invariant(mk, queries)
    assert db.execute(queries[0], observe=False).used_index


def test_shard_invariance_joins():
    gen = QueryGen(SRC, selectivity=0.01, seed=13)
    queries = [gen.high_s() for _ in range(4)]
    ref_db = _mk_db(1)
    ref = [ref_db.execute(q) for q in queries]
    for S in (2, 4):
        db = _mk_db(S)
        got = [db.execute(q) for q in queries]
        for a, b in zip(ref, got):
            assert _stats_key(a) == _stats_key(b)


@pytest.mark.parametrize("num_shards", [1, 3])
def test_join_matches_numpy_after_updates(num_shards):
    """A HIGH-S join counts the pairs of the scan's rows (predicate and
    visibility at its snapshot) with the visible inner rows, after
    UPDATEs have left terminated versions of those rows behind."""
    gen = QueryGen(SRC, selectivity=0.01, seed=17)
    db = _mk_db(num_shards)
    for _ in range(3):
        db.execute(gen.low_u(attr=1, sel=0.02, pos=0.3))
    q = gen.high_s(pos=0.3)
    ts = int(db.clock_ms_i32())
    t = db.tables["narrow"]
    if isinstance(t, ShardedTable):
        t = unshard_table(t)
    data = np.asarray(t.data).reshape(-1, t.n_attrs)
    b, e = (np.asarray(x).reshape(-1) for x in (t.begin_ts, t.end_ts))
    visible = (b <= ts) & (ts < e)
    assert (~visible & (e < 2**31 - 1)).any()   # terminated versions
    outer = visible.copy()
    for a, lo, hi in zip(q.attrs, q.los, q.his):
        outer &= (data[:, a] >= lo) & (data[:, a] <= hi)
    inner = np.sort(data[visible, q.join_inner_attr])
    keys = data[outer, q.join_attr]
    pairs = (np.searchsorted(inner, keys, side="right")
             - np.searchsorted(inner, keys, side="left")).sum()
    assert pairs > 0
    assert db.execute(q).count == int(pairs)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), num_shards=st.integers(2, 4),
       built_frac=st.integers(0, 3))
def test_shard_invariance_randomized_with_mutations(seed, num_shards,
                                                    built_frac):
    """Randomized scan/update/insert mixes across shard counts and
    index build states, including mid-burst mutations."""
    rng = np.random.default_rng(seed)
    gen = QueryGen(SRC, selectivity=float(rng.choice([0.005, 0.05, 0.5])),
                   seed=seed)
    queries = []
    for _ in range(10):
        r = int(rng.integers(5))
        if r == 0:
            queries.append(gen.mod_s())
        elif r == 1:
            queries.append(gen.low_u(attr=int(rng.integers(1, 4))))
        elif r == 2:
            queries.append(gen.ins(n=int(rng.integers(1, 9))))
        else:
            queries.append(gen.low_s(attr=int(rng.integers(1, 4))))

    build = (N_PAGES * built_frac) // 3
    _assert_invariant(
        lambda S: _mk_db(S, "vap" if built_frac else None, build),
        queries, shard_counts=(num_shards,))


# ---------------------------------------------------------------------------
# Full TUNER workload runs (runner + live tuner) across shard counts
# ---------------------------------------------------------------------------

def test_runner_tuner_workload_shard_invariant():
    """The acceptance run: a phased TUNER workload driven by the
    predictive tuner (index creation, VAP builds, drops) produces the
    same per-query latencies and clock for num_shards in {1, 2, 4}."""
    out = {}
    for S in (1, 2, 4):
        gen = QueryGen(SRC, selectivity=0.01, seed=23)
        wl = hybrid_workload(gen, "read_heavy", total=45, phase_len=15,
                             seed=2)
        db = Database(dict(SRC.tables))
        tuner = make_dl_tuner(db, "predictive")
        cfg = RunConfig(tuning_interval_ms=50.0, num_shards=S)
        out[S] = (run_workload(db, tuner, wl, cfg), db)
    ref, ref_db = out[1]
    for S in (2, 4):
        res, db = out[S]
        np.testing.assert_allclose(res.latencies_ms, ref.latencies_ms,
                                   rtol=0, atol=0)
        assert res.phases == ref.phases
        assert res.tuner_work_units == ref.tuner_work_units
        assert res.cumulative_ms == pytest.approx(ref.cumulative_ms, abs=0)
        assert sorted(db.indexes) == sorted(ref_db.indexes)
        assert len(db.monitor.records) == len(ref_db.monitor.records)


def test_runner_read_batch_shard_invariant():
    """Burst submission (read_batch_size > 1) over sharded storage
    matches the unsharded per-query runner."""
    out = {}
    for S, bs in ((1, 1), (2, 8), (4, 8)):
        gen = QueryGen(SRC, selectivity=0.01, seed=29)
        wl = hybrid_workload(gen, "read_heavy", total=40, phase_len=20,
                             seed=4)
        db = Database(dict(SRC.tables))
        cfg = RunConfig(tuning_interval_ms=None, read_batch_size=bs,
                        num_shards=S)
        out[S] = run_workload(db, DisabledTuner(db), wl, cfg)
    for S in (2, 4):
        np.testing.assert_allclose(out[S].latencies_ms, out[1].latencies_ms,
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Kernel hybrid suffix (per-query start_pages through the Pallas kernel)
# ---------------------------------------------------------------------------

def test_kernel_hybrid_suffix_matches_vmapped():
    """use_kernel=True routes the hybrid group's table suffix through
    the multi-query kernel's scalar-prefetched start_pages; results and
    accounting stay bit-identical to the vmapped path."""
    gen = QueryGen(SRC, selectivity=0.01, seed=19)
    queries = [gen.low_s(attr=1) for _ in range(7)]

    def mk():
        return _mk_db(1, "vap", build_pages=N_PAGES // 3)

    a = mk().execute_batch(queries, use_kernel=False)
    b = mk().execute_batch(queries, use_kernel=True)
    for x, y in zip(a, b):
        assert _stats_key(x) == _stats_key(y)
    assert sum(r.used_index for r in a) == len(queries)
    assert any(r.count > 0 for r in a)


# ---------------------------------------------------------------------------
# RunResult guards (write-only / empty runs)
# ---------------------------------------------------------------------------

def test_runresult_empty_latency_guards():
    res = RunResult()
    assert res.mean_latency_ms == 0.0
    assert res.p99_latency_ms == 0.0
    assert res.percentile(50) == 0.0
    s = res.summary()
    assert s["queries"] == 0 and s["p99_latency_ms"] == 0.0


def test_empty_workload_run_summary():
    db = Database(dict(SRC.tables))
    res = run_workload(db, DisabledTuner(db), Workload([]), RunConfig())
    assert res.summary()["queries"] == 0


def test_scheme_result_write_only_summary():
    from benchmarks.common import SchemeResult
    s = SchemeResult(scheme="vap").summary()
    assert s["mean_ms"] == 0.0 and s["p99_ms"] == 0.0 and s["built"] == 0.0
