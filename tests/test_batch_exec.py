"""Oracle tests for the batched query execution path.

Contract: ``Database.execute_batch(queries)`` is bit-identical to
``[db.execute(q) for q in queries]`` -- same aggregates, same cost
accounting, same simulated-clock trajectory -- on randomized read
bursts, including mid-build indexes, mutations between bursts and
mixed MVCC timestamps.  Plus interpret-mode validation of the
multi-query Pallas kernel against its jnp oracle (padding, block-skip
at start_page boundaries, single-query batches).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.bench_db import QueryGen, make_tuner_db
from repro.bench_db.runner import RunConfig, run_workload
from repro.bench_db.workloads import hybrid_workload
from repro.core import Database, IndexDescriptor
from repro.core.baselines import DisabledTuner
from repro.core.hybrid_scan import (_hybrid_scan_core,
                                    batched_full_table_scan,
                                    batched_hybrid_scan)
from repro.core.index import build_pages_vap, eligible_global_pages, make_index
from repro.core.table import load_table
from repro.kernels import ops
from repro.kernels.batched_filter_agg import batched_filter_agg
from repro.kernels.ref import batched_filter_agg_ref, filter_agg_ref

SRC = make_tuner_db(n_rows=4_000, page_size=128)


def _stats_key(s):
    return (s.agg_sum, s.count, s.cost_units, s.latency_ms, s.used_index,
            s.rows_modified)


def _assert_batch_matches_loop(mk_db, queries):
    """Run the same query list through both paths on identical DBs."""
    db_loop, db_batch = mk_db(), mk_db()
    loop = [db_loop.execute(q) for q in queries]
    batch = db_batch.execute_batch(queries)
    for i, (a, b) in enumerate(zip(loop, batch)):
        assert _stats_key(a) == _stats_key(b), (i, queries[i].template, a, b)
    assert db_loop.clock_ms == pytest.approx(db_batch.clock_ms, abs=1e-9)
    return db_loop, db_batch


# ---------------------------------------------------------------------------
# execute_batch vs per-query loop
# ---------------------------------------------------------------------------

def test_batch_32_read_burst_bit_identical():
    """The acceptance burst: >=32 mixed scans over a mid-build index."""
    gen = QueryGen(SRC, selectivity=0.01, seed=3)
    queries = [gen.low_s(attr=1) if i % 3 else gen.mod_s()
               for i in range(40)]

    def mk():
        db = Database(dict(SRC.tables))
        bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
        db.vap_build_step(bi, pages=SRC.tables["narrow"].n_pages // 3)
        return db

    _assert_batch_matches_loop(mk, queries)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), built_frac=st.integers(0, 4),
       sel_pick=st.integers(0, 2))
def test_batch_matches_loop_randomized(seed, built_frac, sel_pick):
    """Randomized bursts across index build states and selectivities
    (non-selective queries exercise the no-index table-scan group)."""
    rng = np.random.default_rng(seed)
    sel = [0.005, 0.05, 0.5][sel_pick]
    gen = QueryGen(SRC, selectivity=sel, seed=seed)
    queries = []
    for _ in range(12):
        r = rng.integers(3)
        queries.append(gen.low_s(attr=int(rng.integers(1, 4))) if r
                       else gen.mod_s())

    def mk():
        db = Database(dict(SRC.tables))
        if built_frac:
            bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
            db.vap_build_step(
                bi, pages=SRC.tables["narrow"].n_pages * built_frac // 4)
        return db

    _assert_batch_matches_loop(mk, queries)


def test_batch_with_mutations_and_mixed_mvcc():
    """Updates/inserts interleaved in the burst list: mutations flush
    the pending scans and execute sequentially, so later scans see the
    new versions at their mixed begin/end timestamps."""
    gen = QueryGen(SRC, selectivity=0.02, seed=11)
    queries = []
    for round_ in range(3):
        queries += [gen.low_s(attr=1) for _ in range(6)]
        queries.append(gen.low_u(attr=1))
        queries.append(gen.ins(n=8))
    queries += [gen.low_s(attr=1) for _ in range(6)]

    db_loop, db_batch = _assert_batch_matches_loop(
        lambda: Database(dict(SRC.tables)), queries)
    # mutations really happened (versions with distinct timestamps)
    ends = np.asarray(db_batch.tables["narrow"].end_ts).reshape(-1)
    assert len({int(e) for e in ends if e < 2**31 - 1}) >= 2


def test_batch_vbp_covered_subdomain():
    """A VBP index with a covered sub-domain serves the burst through
    the batched pure-index-scan group."""
    gen = QueryGen(SRC, selectivity=0.01, seed=7)
    anchor = 0.3
    queries = [gen.low_s(attr=1, pos=anchor) for _ in range(8)]

    def mk():
        db = Database(dict(SRC.tables))
        bi = db.create_index(IndexDescriptor("narrow", (1,)), "vbp")
        db.vbp_populate(bi, queries[0],
                        max_add=SRC.tables["narrow"].capacity)
        return db

    db_loop, _ = _assert_batch_matches_loop(mk, queries)
    assert db_loop.execute(queries[0], observe=False).used_index


def test_batch_kernel_path_matches_vmapped():
    gen = QueryGen(SRC, selectivity=0.01, seed=19)
    queries = [gen.low_s(attr=2) for _ in range(9)]
    db_a, db_b = Database(dict(SRC.tables)), Database(dict(SRC.tables))
    a = db_a.execute_batch(queries, use_kernel=False)
    b = db_b.execute_batch(queries, use_kernel=True)
    for x, y in zip(a, b):
        assert _stats_key(x) == _stats_key(y)


# ---------------------------------------------------------------------------
# execute(scan) is execute_batch([scan]): one operator per plan family
# ---------------------------------------------------------------------------

def _family_db(family, num_shards):
    """A database whose planner serves ``_family_query`` by ``family``."""
    db = Database(dict(SRC.tables), num_shards=num_shards)
    db.index_decay = family == "hybrid_masked"   # attaches the bitmap
    desc = IndexDescriptor("narrow", (1,))
    n_pages = SRC.tables["narrow"].n_pages
    if family in ("hybrid", "hybrid_masked"):
        bi = db.create_index(desc, "vap")
        picks = eligible_global_pages(db.tables["narrow"])[::3]
        db.vap_build_step(bi, pages=len(picks), page_list=(
            picks if family == "hybrid_masked" else None))
    elif family == "hybrid_ps":
        bi = db.create_index(desc, "vap")
        for shard, pages in ((0, 5), (2, 2)):
            db.vap_build_step(bi, pages=pages, shard=shard)
    elif family == "pure_vap":
        db.vap_build_step(db.create_index(desc, "full"), pages=n_pages)
    elif family == "pure_vbp":
        bi = db.create_index(desc, "vbp")
        for k in range(3):
            db.vbp_populate(bi, _family_query(k),
                            max_add=SRC.tables["narrow"].capacity)
    return db


def _family_query(k):
    gen = QueryGen(SRC, selectivity=0.01, seed=31 + k)
    return gen.low_s(attr=1, pos=0.3 + 0.01 * k)


@pytest.mark.parametrize("family,num_shards", [
    (family, num_shards)
    for num_shards in (1, 3)
    for family in ("table", "hybrid", "hybrid_ps", "hybrid_masked",
                   "pure_vap", "pure_vbp")
    # the per-shard stitch exists on sharded tables only
    if num_shards > 1 or family != "hybrid_ps"
])
def test_execute_is_batch_of_one(family, num_shards):
    """Every ExecStats field of ``execute(scan)`` equals
    ``execute_batch([scan])[0]``, and so do the clock and the monitor,
    for each plan family on a plain and a 3-shard table."""
    db_one, db_batch = _family_db(family, num_shards), \
        _family_db(family, num_shards)
    for k in range(3):
        q = _family_query(k)
        plan = db_one.planner.plan_scan(q)
        assert plan.path == family or (
            family == "table" and plan.index is None), plan.path
        a = db_one.execute(q)
        (b,) = db_batch.execute_batch([q])
        assert a == b, (family, num_shards, k, a, b)
    assert db_one.clock_ms == db_batch.clock_ms
    assert list(db_one.monitor.records) == list(db_batch.monitor.records)


# ---------------------------------------------------------------------------
# batched scan operators vs one call of the per-query body (accounting)
# ---------------------------------------------------------------------------

def test_batched_hybrid_scan_accounting_fields():
    rng = np.random.default_rng(2)
    vals = rng.integers(0, 100, size=(60, 4)).astype(np.int32)
    t = load_table(vals, page_size=8, n_pages=11)
    idx = make_index(capacity=t.capacity)
    idx = build_pages_vap(idx, t, key_attrs=(1,), pages_per_cycle=3)
    los = np.array([[0], [20], [90], [50]], np.int32)
    his = np.array([[99], [40], [95], [50]], np.int32)
    tss = np.zeros(4, np.int32)
    r = batched_hybrid_scan(t, idx, (1,), (1,), jnp.asarray(los),
                            jnp.asarray(his), jnp.asarray(tss), 2)
    for k in range(4):
        stats, *_ = _hybrid_scan_core(t, idx, (1,), (1,),
                                      jnp.asarray(los[k]),
                                      jnp.asarray(his[k]), 0, 2)
        got = [int(field[k]) for field in r]
        assert got == [int(x) for x in stats], (k, got, stats)


def test_batched_full_scan_accounting_fields():
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 100, size=(50, 4)).astype(np.int32)
    t = load_table(vals, page_size=8)
    los = np.array([[10, 0], [0, 50]], np.int32)
    his = np.array([[90, 99], [99, 60]], np.int32)
    r = batched_full_table_scan(t, (1, 2), jnp.asarray(los),
                                jnp.asarray(his),
                                jnp.zeros(2, jnp.int32), 3)
    used_pages = -(-50 // 8)
    for k in range(2):
        ref_sum, ref_count = filter_agg_ref(
            t.data[:, :, 1], t.data[:, :, 2], t.data[:, :, 3], t.begin_ts,
            t.end_ts, los[k, 0], his[k, 0], los[k, 1], his[k, 1], 0)
        got = [int(field[k]) for field in r]
        assert got == [int(ref_sum), int(ref_count), used_pages, 0, 0]


# ---------------------------------------------------------------------------
# multi-query Pallas kernel (interpret mode) vs jnp oracle
# ---------------------------------------------------------------------------

def _mk_planes(n_rows, page_size, seed=0):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 1000, size=(n_rows, 5)).astype(np.int32)
    return load_table(vals, page_size=page_size)


@pytest.mark.parametrize("n_rows,page_size", [(256, 128), (1000, 128),
                                              (130, 128), (511, 128)])
def test_batched_kernel_matches_ref_with_padding(n_rows, page_size):
    """Page counts that are not block multiples exercise the pad path."""
    t = _mk_planes(n_rows, page_size, seed=n_rows)
    rng = np.random.default_rng(n_rows + 1)
    B = 6
    los0 = rng.integers(0, 500, size=B).astype(np.int32)
    his0 = los0 + rng.integers(0, 400, size=B).astype(np.int32)
    tss = np.zeros(B, np.int32)
    sps = rng.integers(0, t.n_pages + 2, size=B).astype(np.int32)
    lo1 = np.full(B, ops.I32_MIN, np.int32)
    hi1 = np.full(B, ops.I32_MAX, np.int32)
    args = (t.data[:, :, 1], t.data[:, :, 1], t.data[:, :, 4],
            t.begin_ts, t.end_ts, jnp.asarray(los0), jnp.asarray(his0),
            jnp.asarray(lo1), jnp.asarray(hi1), jnp.asarray(tss),
            jnp.asarray(sps))
    s, c = batched_filter_agg(*args, block_pages=8, interpret=True)
    rs, rc = batched_filter_agg_ref(*args)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))


def test_batched_kernel_block_skip_boundaries():
    """start_page at block boundaries, mid-block, 0 and past-the-end in
    ONE batch -- each query must mask independently."""
    t = _mk_planes(3000, 128, seed=9)
    bp = 8
    boundaries = [0, 1, bp - 1, bp, bp + 1, 2 * bp, t.n_pages - 1,
                  t.n_pages, t.n_pages + 5]
    B = len(boundaries)
    los0 = np.zeros(B, np.int32)
    his0 = np.full(B, 999, np.int32)
    args = (t.data[:, :, 1], t.data[:, :, 1], t.data[:, :, 2],
            t.begin_ts, t.end_ts, jnp.asarray(los0), jnp.asarray(his0),
            jnp.full(B, ops.I32_MIN, jnp.int32),
            jnp.full(B, ops.I32_MAX, jnp.int32),
            jnp.zeros(B, jnp.int32),
            jnp.asarray(boundaries, jnp.int32))
    s, c = batched_filter_agg(*args, block_pages=bp, interpret=True)
    rs, rc = batched_filter_agg_ref(*args)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))
    # fully-skipped query (start past the end) returns exactly zero
    assert int(c[-1]) == 0 and int(s[-1]) == 0


def test_batched_kernel_single_query_matches_single_kernel():
    """A batch of one through the ops wrapper, two predicate columns,
    with and without a stitch point, against the jnp oracle."""
    t = _mk_planes(512, 128, seed=4)
    for sp in (0, 3):
        sb, cb = ops.scan_table_batched(
            t, (1, 2), np.array([[100, 200]], np.int32),
            np.array([[700, 900]], np.int32), np.zeros(1, np.int32), 4,
            start_pages=np.array([sp], np.int32))
        rs, rc = batched_filter_agg_ref(
            t.data[:, :, 1], t.data[:, :, 2], t.data[:, :, 4], t.begin_ts,
            t.end_ts, jnp.array([100]), jnp.array([700]), jnp.array([200]),
            jnp.array([900]), jnp.zeros(1, jnp.int32), jnp.array([sp]))
        assert (int(sb[0]), int(cb[0])) == (int(rs[0]), int(rc[0]))


def test_batched_kernel_respects_mvcc_per_query():
    """Different snapshots in one batch see different version sets."""
    from repro.core.table import update_rows
    t = _mk_planes(512, 128, seed=13)
    t2, _ = update_rows(t, (1,), jnp.array([0]), jnp.array([400]),
                        jnp.array([2]), jnp.array([9999]), ts=10,
                        max_new=64)
    tss = np.array([5, 15], np.int32)
    B = 2
    args = (t2.data[:, :, 1], t2.data[:, :, 1], t2.data[:, :, 2],
            t2.begin_ts, t2.end_ts,
            jnp.zeros(B, jnp.int32), jnp.full(B, 999, jnp.int32),
            jnp.full(B, ops.I32_MIN, jnp.int32),
            jnp.full(B, ops.I32_MAX, jnp.int32),
            jnp.asarray(tss), jnp.zeros(B, jnp.int32))
    s, c = batched_filter_agg(*args, block_pages=8, interpret=True)
    rs, rc = batched_filter_agg_ref(*args)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))
    assert int(s[0]) != int(s[1])   # the update is visible only at ts=15


# ---------------------------------------------------------------------------
# bench runner read bursts
# ---------------------------------------------------------------------------

def test_runner_read_batch_matches_unbatched():
    """With tuning disabled, the batched runner produces the same
    per-query latencies as the per-query runner."""
    gen = QueryGen(SRC, selectivity=0.01, seed=23)
    wl = hybrid_workload(gen, "read_heavy", total=60, phase_len=30, seed=2)
    out = {}
    for bs in (1, 16):
        db = Database(dict(SRC.tables))
        cfg = RunConfig(tuning_interval_ms=None, read_batch_size=bs)
        out[bs] = run_workload(db, DisabledTuner(db), wl, cfg)
    assert len(out[1].latencies_ms) == len(out[16].latencies_ms) == 60
    np.testing.assert_allclose(out[1].latencies_ms, out[16].latencies_ms,
                               rtol=0, atol=1e-12)
    assert out[1].phases == out[16].phases
