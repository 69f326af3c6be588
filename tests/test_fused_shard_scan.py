"""Fused single-dispatch sharded scans: parity + cache invariants.

Contract: the stacked single-dispatch forms (and the fused Pallas
kernel in interpret mode) are BIT-identical to a per-shard loop
fan-out over the shards tuple (``loop_reference`` below) for every
batched scan family, for uniform round-robin AND skewed pre-sharded
layouts, including ``hybrid_ps`` with divergent per-shard built
prefixes -- in results and in every accounting field.
The stacked/padded shard pytree is cached per shards-tuple identity
and invalidated by every mutator.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.shard_tuning import make_skewed_db
from repro.bench_db import QueryGen, make_tuner_db
from repro.core import Database, IndexDescriptor
from repro.core import engine as eng
from repro.core.index import stacked_shard_indexes
from repro.core.table import (ShardedTable, sharded_insert_rows,
                              sharded_update_rows, stacked_shards)

SRC = make_tuner_db(n_rows=3_000, page_size=128)


def _mk_db(num_shards=4, build_pages=0, shard_builds=()):
    db = Database(dict(SRC.tables), num_shards=num_shards)
    bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
    if build_pages:
        db.vap_build_step(bi, pages=build_pages)
    for shard, pages in shard_builds:
        db.vap_build_step(bi, pages=pages, shard=shard)
    return db, bi


def _mk_skewed_db(shard_builds=((0, 10), (2, 4))):
    src = make_skewed_db()          # 36/4/4/4-page shards
    db = Database(dict(src.tables))
    bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
    for shard, pages in shard_builds:
        db.vap_build_step(bi, pages=pages, shard=shard)
    return db, bi


def _bounds(n_queries, seed=0, width=20_000, two_attr=False):
    rng = np.random.default_rng(seed)
    los = rng.integers(1, 5 * 10**5, size=(n_queries, 1)).astype(np.int32)
    his = los + width
    if two_attr:
        los = np.concatenate(
            [los, np.zeros((n_queries, 1), np.int32)], axis=1)
        his = np.concatenate(
            [his, np.full((n_queries, 1), 10**6, np.int32)], axis=1)
    tss = np.full((n_queries,), 5, np.int32)
    return jnp.asarray(los), jnp.asarray(his), jnp.asarray(tss)


def _assert_results_equal(a, b, label):
    for field, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{label}.{field}")


FAMILIES = ("table", "hybrid", "hybrid_ps", "pure_vap")
I32_MAX = 2**31 - 1


def loop_reference(path, st, ix, los, his, tss):
    """The per-shard loop fan-out on key and predicate a_1, SUM(a_2):
    each unpadded shard of the tuple runs the batch on its own, and the
    partials reduce in shard order.  The stacked forms' reference."""
    S, B = st.n_shards, los.shape[0]
    shards = list(zip(st.shards, ix.shards))
    probe = functools.partial(eng._shard_index_probe, S=S, key_attrs=(1,),
                              attrs=(1,))
    if path == "hybrid":  # pass 1: the global stitch point
        rho = [jax.vmap(lambda lo, hi, ts, t=t, x=x, s=s: probe(
            t, x, s, lo=lo, hi=hi, ts=ts)[5])(los, his, tss)
            for s, (t, x) in enumerate(shards)]
        starts = jnp.maximum(functools.reduce(jnp.maximum, rho),
                             ix.built_pages)
    else:  # a table scan starts at 0; an index-only scan never starts
        starts = jnp.full((B,), 0 if path == "table" else I32_MAX)

    def one(t, x, s, lo, hi, ts, sp):
        z = jnp.zeros((), jnp.int32)
        if path == "hybrid_ps":
            keep, pg, sl, ents, tbl, pages, gstart = eng._pershard_stitch(
                t, x, s, S, (1,), (1,), lo, hi, ts)
        else:
            tbl = eng._shard_table_mask(t, s, S, (1,), lo, hi, ts, sp)
            match, gpg, pg, sl, ents, _ = probe(t, x, s, lo=lo, hi=hi, ts=ts)
            keep = match & (gpg < sp) & (path != "table")
            ents, pages, gstart = ents & (path != "table"), z, z
        vals = t.data[:, :, 2]
        return (jnp.sum(jnp.where(keep, vals[pg, sl], 0), dtype=jnp.int32)
                + jnp.sum(jnp.where(tbl, vals, 0), dtype=jnp.int32),
                jnp.sum(keep, dtype=jnp.int32) + jnp.sum(tbl, dtype=jnp.int32),
                jnp.sum(ents, dtype=jnp.int32), pages, gstart)

    parts = [jax.vmap(functools.partial(one, t, x, s))(los, his, tss, starts)
             for s, (t, x) in enumerate(shards)]
    sums, cnts, ents, pages, gstarts = (
        functools.reduce(op, p) for op, p in zip(
            (jnp.add,) * 4 + (jnp.minimum,), zip(*parts)))
    used = jnp.full((B,), eng._used_pages(st))
    zeros = jnp.zeros((B,), jnp.int32)
    return eng.BatchScanResult(sums, cnts, *{
        "table": (used, ents, zeros),
        "hybrid": (jnp.clip(used - starts, 0, None), ents, starts),
        "hybrid_ps": (pages, ents, gstarts),
        "pure_vap": (zeros, ents, jnp.full((B,), st.n_pages)),
    }[path])


STACKED_FNS = {
    "table": eng.sharded_batched_full_table_scan,
    "hybrid": eng.sharded_batched_hybrid_scan,
    "hybrid_ps": eng.sharded_batched_hybrid_scan_pershard,
    "pure_vap": eng.sharded_batched_pure_index_scan,
}


def _run_family(fn, path, st, ix, los, his, tss):
    if path == "table":
        return fn(st, (1,), los, his, tss, 2)
    return fn(st, ix, (1,), (1,), los, his, tss, 2)


# ---------------------------------------------------------------------------
# Stacked single dispatch vs per-shard loop fan-out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [2, 3, 4])
@pytest.mark.parametrize("path", FAMILIES)
def test_stacked_matches_loop_uniform(num_shards, path):
    db, bi = _mk_db(num_shards=num_shards, build_pages=9)
    st = db.tables["narrow"]
    los, his, tss = _bounds(6, seed=num_shards)
    a = loop_reference(path, st, bi.vap, los, his, tss)
    b = _run_family(STACKED_FNS[path], path, st, bi.vap, los, his, tss)
    _assert_results_equal(a, b, f"{path}@S{num_shards}")


@pytest.mark.parametrize("path", ("table", "hybrid_ps", "pure_vap"))
def test_stacked_matches_loop_skewed(path):
    """36/4/4/4-page pre-sharded layout with divergent per-shard built
    prefixes: padding correctness for ragged shards + the relaxed
    prefix invariant."""
    db, bi = _mk_skewed_db()
    st = db.tables["narrow"]
    assert len({t.n_pages for t in st.shards}) > 1  # genuinely ragged
    los, his, tss = _bounds(5, seed=11, width=40_000)
    a = loop_reference(path, st, bi.vap, los, his, tss)
    b = _run_family(STACKED_FNS[path], path, st, bi.vap, los, his, tss)
    _assert_results_equal(a, b, f"skewed.{path}")


def test_stacked_hybrid_ps_divergent_prefixes():
    """Per-shard builds that diverge from the global round-robin
    prefix: the stacked per-shard stitch must agree with the loop
    stitch on every accounting field (incl. the min-gstart report)."""
    db, bi = _mk_db(num_shards=4, shard_builds=((0, 5), (3, 2)))
    assert bi.desc.name in db.pershard_built
    st = db.tables["narrow"]
    los, his, tss = _bounds(8, seed=23)
    a = loop_reference("hybrid_ps", st, bi.vap, los, his, tss)
    b = _run_family(STACKED_FNS["hybrid_ps"], "hybrid_ps", st, bi.vap,
                    los, his, tss)
    _assert_results_equal(a, b, "divergent.hybrid_ps")


# ---------------------------------------------------------------------------
# Fused kernel (interpret mode) vs the vmapped jnp forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("two_attr", [False, True])
@pytest.mark.parametrize("path", FAMILIES)
def test_kernel_matches_jnp_uniform(path, two_attr):
    db, bi = _mk_db(num_shards=4, build_pages=9)
    st = db.tables["narrow"]
    attrs = (1, 2) if two_attr else (1,)
    agg = 3 if two_attr else 2
    los, his, tss = _bounds(6, seed=7, two_attr=two_attr)
    e = eng.ScanEngine()
    r_jnp = e.scan_batch(st, path, bi.vap, (1,), attrs, los, his, tss,
                         agg, use_kernel=False)
    r_ker = e.scan_batch(st, path, bi.vap, (1,), attrs, los, his, tss,
                         agg, use_kernel=True)
    _assert_results_equal(r_jnp, r_ker, f"kernel.{path}.2attr={two_attr}")


@pytest.mark.parametrize("path", FAMILIES)
def test_kernel_matches_jnp_skewed(path):
    db, bi = _mk_skewed_db()
    st = db.tables["narrow"]
    los, his, tss = _bounds(5, seed=13, width=40_000)
    e = eng.ScanEngine()
    r_jnp = e.scan_batch(st, path, bi.vap, (1,), (1,), los, his, tss, 2,
                         use_kernel=False)
    r_ker = e.scan_batch(st, path, bi.vap, (1,), (1,), los, his, tss, 2,
                         use_kernel=True)
    _assert_results_equal(r_jnp, r_ker, f"kernel.skewed.{path}")


def test_kernel_burst_database_invariant():
    """Database-level: kernel bursts replay identical results AND
    cost/clock/monitor accounting vs the per-query loop, sharded."""
    gen = QueryGen(SRC, selectivity=0.01, seed=3)
    queries = [gen.low_s(attr=1) for _ in range(8)]
    ref_db, _ = _mk_db(num_shards=1, build_pages=9)
    ref = [ref_db.execute(q) for q in queries]
    db, _ = _mk_db(num_shards=4, build_pages=9)
    got = db.execute_batch(queries, use_kernel=True)
    for a, b in zip(ref, got):
        assert (a.agg_sum, a.count, a.cost_units, a.latency_ms) == \
            (b.agg_sum, b.count, b.cost_units, b.latency_ms)
    assert db.clock_ms == pytest.approx(ref_db.clock_ms, abs=1e-9)
    assert list(db.monitor.records) == list(ref_db.monitor.records)


# ---------------------------------------------------------------------------
# Stacked pytree cache: identity reuse + invalidation on mutation
# ---------------------------------------------------------------------------

def test_stacked_cache_reuse_and_mutation_invalidation():
    db, bi = _mk_db(num_shards=4, build_pages=4)
    st = db.tables["narrow"]
    stk1 = stacked_shards(st)
    assert stacked_shards(st) is stk1             # cache hit
    six1 = stacked_shard_indexes(bi.vap)
    assert stacked_shard_indexes(bi.vap) is six1

    # INSERT invalidates the table stack (new shards tuple).
    rows = jnp.zeros((4, st.n_attrs), jnp.int32)
    st2 = sharded_insert_rows(st, rows, 7, 2, max_new=4)
    stk2 = stacked_shards(st2)
    assert stk2 is not stk1
    # UPDATE likewise.
    st3, _ = sharded_update_rows(
        st2, (1,), jnp.asarray([1]), jnp.asarray([50]),
        jnp.asarray([2]), jnp.asarray([9]), 9, max_new=4)
    assert stacked_shards(st3) is not stk2
    # Build quanta replace the index shards tuple.
    db.vap_build_step(bi, pages=2)
    assert stacked_shard_indexes(bi.vap) is not six1
    # Padded geometry survives the round trip.
    assert stk1.table.data.shape[0] == st.n_shards
    assert int(jnp.sum(stk1.local_pages)) == st.n_pages


def test_stacked_padding_is_invisible():
    """Ragged shards pad to a uniform page grid; padding pages must
    never match any snapshot (begin_ts == NEVER_TS)."""
    db, _ = _mk_skewed_db(shard_builds=())
    st = db.tables["narrow"]
    stk = stacked_shards(st)
    max_pages = int(stk.table.data.shape[1])
    for s, t in enumerate(st.shards):
        pad = max_pages - t.n_pages
        if pad:
            padded = np.asarray(stk.table.begin_ts[s, t.n_pages:])
            assert (padded == np.int32(2**31 - 1)).all()


# ---------------------------------------------------------------------------
# Adaptive cycle sizing from the build lane's measured throughput
# ---------------------------------------------------------------------------

def test_suggested_pages_per_cycle_tracks_throughput():
    from repro.core.build_service import BuildService

    svc = BuildService(db=None, tuner=None)
    assert svc.suggested_pages_per_cycle() is None   # no measurement yet
    svc.pages_per_ms = 5.0
    assert svc.suggested_pages_per_cycle(target_ms=4.0) == 20
    svc.pages_per_ms = 0.01
    assert svc.suggested_pages_per_cycle(target_ms=4.0) == 1  # floor


def test_adaptive_build_budget_resizes_pages_per_cycle():
    from repro.bench_db.runner import RunConfig, run_workload
    from repro.bench_db.workloads import hybrid_workload
    from repro.core import PredictiveTuner, TunerConfig

    src = make_tuner_db(n_rows=3_000, page_size=128)
    gen = QueryGen(src, selectivity=0.01, seed=5)
    wl = hybrid_workload(gen, "read_only", total=120)
    db = Database(dict(src.tables))
    cfg_t = TunerConfig(pages_per_cycle=4, max_build_pages_per_cycle=16)
    tuner = PredictiveTuner(db, cfg_t)
    cfg = RunConfig(tuning_interval_ms=20.0, read_batch_size=8,
                    async_tuning="overlap", adaptive_build_budget=True,
                    arrival_ms=1.0)
    res = run_workload(db, tuner, wl, cfg)
    if res.build_pages_per_ms > 0.0:    # a drain happened and measured
        assert 1 <= tuner.cfg.pages_per_cycle <= 16
        assert res.build_pages_per_cycle == tuner.cfg.pages_per_cycle

    # Flag off: the configured budget is never touched.
    db2 = Database(dict(src.tables))
    tuner2 = PredictiveTuner(
        db2, TunerConfig(pages_per_cycle=4, max_build_pages_per_cycle=16))
    cfg2 = RunConfig(tuning_interval_ms=20.0, read_batch_size=8,
                     async_tuning="overlap", arrival_ms=1.0)
    run_workload(db2, tuner2, wl, cfg2)
    assert tuner2.cfg.pages_per_cycle == 4
