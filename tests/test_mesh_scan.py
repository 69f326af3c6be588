"""Mesh execution tests: shard_map dispatch over the stacked-shard
pytree.

Contract: on any device mesh the four batched scan families (full
table / hybrid / per-shard hybrid / pure index) are bit-identical --
every BatchScanResult field, so cost/clock/monitor accounting too --
to the single-device stacked vmap path, which is itself pinned to the
per-shard loop oracle by test_fused_shard_scan.  Device counts are
forced via ``--xla_force_host_platform_device_count`` in fresh
subprocesses (XLA reads it at import time).  The in-process half
covers placement fallback, the mesh_mode=True hard-require knob, and
the execution-tier telemetry (no silent downgrade to another tier).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.bench_db import QueryGen, make_tuner_db
from repro.core import Database
from repro.core.cost_model import allocate_cycle_budget
from repro.serving.admission import bursty_arrivals, make_arrivals

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _env(n_devices):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, os.path.join(ROOT, "tests")]
    )
    return env


def _run(script, n_devices, token, timeout=420):
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        env=_env(n_devices), capture_output=True, text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert token in proc.stdout, (proc.stdout, proc.stderr)


# The family sweep shared by the device-count variants below: every
# family, uniform AND skewed (36/4/4/4) shard layouts, divergent
# per-shard built prefixes, mesh result compared field-by-field
# against the stacked single-device dispatch.
_IDENTITY_SCRIPT = """
    import jax
    from test_fused_shard_scan import (FAMILIES, STACKED_FNS, _bounds,
                                       _assert_results_equal, _mk_db,
                                       _mk_skewed_db, _run_family)
    from repro.core import engine as eng
    from repro.parallel.mesh import QUERY_AXIS, make_scan_mesh

    N_DEV = %d
    assert len(jax.devices()) == N_DEV, jax.devices()

    MESH_FNS = {
        "table": eng.mesh_batched_full_table_scan,
        "hybrid": eng.mesh_batched_hybrid_scan,
        "hybrid_ps": eng.mesh_batched_hybrid_scan_pershard,
        "pure_vap": eng.mesh_batched_pure_index_scan,
    }

    def run_mesh(fam, st, ix, los, his, tss, mesh):
        if fam == "table":
            return MESH_FNS[fam](st, (1,), los, his, tss, 2, mesh)
        return MESH_FNS[fam](st, ix, (1,), (1,), los, his, tss, 2, mesh)

    cases = [
        ("uniform4", lambda: _mk_db(4, shard_builds=((0, 3), (2, 1)))),
        ("skewed", lambda: _mk_skewed_db()),
    ]
    for name, mk in cases:
        db, bi = mk()
        st = db.tables["narrow"]
        ix = db.indexes["narrow:1"].vap
        los, his, tss = _bounds(6)
        meshes = [("1d", make_scan_mesh(st.n_shards))]
        if N_DEV >= 4:
            m2 = make_scan_mesh(st.n_shards, query_axis=2)
            assert m2 is not None and QUERY_AXIS in m2.axis_names
            meshes.append(("2d", m2))
        for mname, mesh in meshes:
            assert mesh is not None, (name, mname)
            for fam in FAMILIES:
                a = _run_family(STACKED_FNS[fam], fam, st, ix,
                                los, his, tss)
                b = run_mesh(fam, st, ix, los, his, tss, mesh)
                _assert_results_equal(a, b, f"{name}.{mname}.{fam}")
    print("MESH_IDENTITY_OK")
"""


# One statement at a time on 4 shards: each scan dispatches as a batch
# of one onto the mesh (tier shard_map) and matches the one-shard
# engine in results, accounting and monitor records.
_ONE_STATEMENT_SCRIPT = """
    from repro.bench_db import QueryGen, make_tuner_db
    from repro.core import Database, IndexDescriptor

    SRC = make_tuner_db(n_rows=2_000, page_size=128)
    qs = [QueryGen(SRC, selectivity=sel, seed=3).low_s(attr=1)
          for sel in (0.01, 0.01, 0.5, 0.01, 0.5, 0.01)]

    def mk(num_shards):
        db = Database(dict(SRC.tables), num_shards=num_shards)
        bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
        db.vap_build_step(bi, pages=5)
        return db

    def key(s):
        return (s.agg_sum, s.count, s.cost_units, s.latency_ms,
                s.used_index)

    ref_db, db = mk(1), mk(4)
    ref = [ref_db.execute(q) for q in qs]
    got = [db.execute(q) for q in qs]
    assert [key(s) for s in got] == [key(s) for s in ref], (got, ref)
    assert all(s.tier == "shard_map" for s in got), got
    assert {s.used_index for s in got} == {True, False}
    assert db.clock_ms == ref_db.clock_ms
    assert list(db.monitor.records) == list(ref_db.monitor.records)
    print("MESH_IDENTITY_OK")
"""


@pytest.mark.parametrize(
    "script",
    [_IDENTITY_SCRIPT % 4, _ONE_STATEMENT_SCRIPT],
    ids=["families", "one_statement"],
)
def test_mesh_bit_identity_4dev_subprocess(script):
    """4 shards on a 4-device mesh (one shard per device): the four
    families batched, on the 1-D and the 2-D shard x query-batch mesh;
    and single statements through ``execute``, each a batch of one."""
    _run(script, 4, "MESH_IDENTITY_OK")


def test_mesh_bit_identity_2dev_subprocess():
    """4 shards folded onto 2 devices (2 local shards per device):
    the collectives run over a genuinely partial reduction."""
    _run(_IDENTITY_SCRIPT % 2, 2, "MESH_IDENTITY_OK")


def test_mesh_database_accounting_4dev_subprocess():
    """Database-level run on a forced 4-device mesh: per-query stats,
    clock, and monitor records match the single-shard engine; the
    execution tier is recorded as shard_map (auto), vmap-stacked when
    mesh=False, and RunResult.execution_tiers tallies both."""
    script = """
        import numpy as np
        from repro.bench_db import QueryGen, make_tuner_db
        from repro.bench_db.runner import RunConfig, run_workload
        from repro.bench_db.workloads import hybrid_workload
        from repro.core import Database, make_dl_tuner

        SRC = make_tuner_db(n_rows=2_000, page_size=128)
        gen = QueryGen(SRC, selectivity=0.01, seed=3)
        qs = [gen.low_s(attr=1) for _ in range(6)]

        def key(s):
            return (s.agg_sum, s.count, s.cost_units, s.latency_ms,
                    s.used_index, s.tier)

        ref_db = Database(dict(SRC.tables))
        ref = [key(r) for r in ref_db.execute_batch(qs)]
        db = Database(dict(SRC.tables), num_shards=4)
        got = [key(r) for r in db.execute_batch(qs)]
        assert db.engine.last_tier == "shard_map", db.engine.last_tier
        assert [k[:5] for k in got] == [k[:5] for k in ref], (got, ref)
        assert all(k[5] == "shard_map" for k in got)
        assert db.clock_ms == ref_db.clock_ms
        assert list(db.monitor.records) == list(ref_db.monitor.records)

        # mesh=False forces the single-device stacked dispatch
        db2 = Database(dict(SRC.tables), num_shards=4)
        db2.engine.mesh_mode = False
        got2 = [key(r) for r in db2.execute_batch(qs)]
        assert [k[:5] for k in got2] == [k[:5] for k in ref]
        assert all(k[5] == "vmap-stacked" for k in got2), got2

        # full workload: tier tally lands on RunResult, accounting
        # matches the single-shard run bit for bit
        def run(num_shards, mesh):
            db = Database(dict(SRC.tables), num_shards=num_shards)
            gen = QueryGen(SRC, selectivity=0.01, seed=5)
            wl = hybrid_workload(gen, "read_only", total=40,
                                 phase_len=20)
            cfg = RunConfig(read_batch_size=8, num_shards=num_shards,
                            mesh=mesh)
            return run_workload(db, make_dl_tuner(db, "predictive"),
                                wl, cfg)

        r1 = run(1, None)
        r4 = run(4, None)
        assert r4.execution_tiers.get("shard_map", 0) > 0, \\
            r4.execution_tiers
        np.testing.assert_array_equal(
            np.asarray(r4.latencies_ms), np.asarray(r1.latencies_ms))
        assert r4.cumulative_ms == r1.cumulative_ms
        rf = run(4, False)
        assert "shard_map" not in rf.execution_tiers, rf.execution_tiers
        assert rf.execution_tiers.get("vmap-stacked", 0) > 0
        np.testing.assert_array_equal(
            np.asarray(rf.latencies_ms), np.asarray(r1.latencies_ms))
        print("MESH_DB_OK")
    """
    _run(script, 4, "MESH_DB_OK")


def test_mesh_fallback_and_require_1dev_subprocess():
    """On a single device there is no mesh placement: auto mode falls
    back to the stacked dispatch (tier telemetry says so -- no silent
    lie), and mesh_mode=True raises instead of downgrading."""
    script = """
        import jax
        from repro.bench_db import QueryGen, make_tuner_db
        from repro.core import Database
        from repro.parallel.mesh import make_scan_mesh

        assert len(jax.devices()) == 1
        assert make_scan_mesh(4) is None
        assert make_scan_mesh(4, query_axis=2) is None

        SRC = make_tuner_db(n_rows=2_000, page_size=128)
        gen = QueryGen(SRC, selectivity=0.01, seed=3)
        qs = [gen.low_s(attr=1) for _ in range(4)]
        db = Database(dict(SRC.tables), num_shards=4)
        stats = db.execute_batch(qs)
        assert db.engine.last_tier == "vmap-stacked", db.engine.last_tier
        assert all(s.tier == "vmap-stacked" for s in stats)

        db2 = Database(dict(SRC.tables), num_shards=4)
        db2.engine.mesh_mode = True
        try:
            db2.execute_batch(qs)
        except RuntimeError as e:
            assert "mesh" in str(e), e
        else:
            raise AssertionError("mesh_mode=True did not raise")
        print("MESH_FALLBACK_OK")
    """
    _run(script, 1, "MESH_FALLBACK_OK")


# ---------------------------------------------------------------------------
# In-process: tier telemetry on the single-device paths
# ---------------------------------------------------------------------------

def test_exec_stats_tier_recorded_inprocess():
    """Every ExecStats carries the tier of the dispatch that served
    it, including the plain-table single-query path."""
    src = make_tuner_db(n_rows=1_000, page_size=128)
    gen = QueryGen(src, selectivity=0.01, seed=7)
    db = Database(dict(src.tables))
    s = db.execute(gen.low_s(attr=1))
    assert s.tier == "single"
    stats = db.execute_batch([gen.low_s(attr=1) for _ in range(3)])
    assert all(s.tier for s in stats)


# ---------------------------------------------------------------------------
# Satellite: cross-index cycle-budget allocator determinism
# ---------------------------------------------------------------------------

def test_allocate_cycle_budget_deterministic_and_exact():
    utils = [3.0, 0.0, 11.5, 0.25]
    remaining = [100, 50, 2, 100]
    a = allocate_cycle_budget(utils, remaining, budget=64, per_index_cap=32)
    b = allocate_cycle_budget(utils, remaining, budget=64, per_index_cap=32)
    np.testing.assert_array_equal(a, b)
    assert int(a.sum()) == 64  # budget fully spent when work remains
    assert all(0 <= x <= 32 for x in a)
    assert a[2] <= 2  # never over a build's remaining pages
    # higher forecast utility never gets fewer pages (same remaining)
    assert a[0] >= a[3]


def test_allocate_cycle_budget_edge_cases():
    # finished builds draw nothing; budget redistributes to the rest
    a = allocate_cycle_budget([5.0, 9.0], [0, 40], budget=32,
                              per_index_cap=32)
    assert list(a) == [0, 32]
    # single building index keeps the legacy per-cycle step
    a = allocate_cycle_budget([0.0], [1000], budget=64, per_index_cap=32)
    assert list(a) == [32]
    # two equal-utility builds split the legacy 32+32 schedule
    a = allocate_cycle_budget([1.0, 1.0], [500, 500], budget=64,
                              per_index_cap=32)
    assert list(a) == [32, 32]
    # scarce budget: weighted largest-remainder, still exact
    a = allocate_cycle_budget([8.0, 1.0, 1.0], [90, 90, 90], budget=10,
                              per_index_cap=32)
    assert int(a.sum()) == 10 and a[0] > a[1]


# ---------------------------------------------------------------------------
# Satellite: serving stream shape knobs
# ---------------------------------------------------------------------------

def test_make_arrivals_knob_defaults_bit_identical():
    """Default peak_ratio/on_frac/tenants reproduce the historical
    bursty stream bit for bit."""
    old = bursty_arrivals(200, 4.0, seed=11, peak_ratio=8.0, on_frac=0.125)
    new = make_arrivals("bursty", 200, 4.0, seed=11)
    np.testing.assert_array_equal(new, old)


def test_make_arrivals_shape_knobs():
    base = make_arrivals("bursty", 300, 4.0, seed=2)
    hot = make_arrivals("bursty", 300, 4.0, seed=2, peak_ratio=32.0,
                        on_frac=0.05)
    assert hot.shape == base.shape
    assert not np.array_equal(hot, base)
    # sharper peaks => burstier gaps at matched long-run mean rate
    assert np.std(np.diff(hot)) > np.std(np.diff(base))


def test_make_arrivals_multi_tenant():
    one = make_arrivals("bursty", 400, 4.0, seed=5)
    mix = make_arrivals("bursty", 400, 4.0, seed=5, tenants=4)
    assert mix.shape == one.shape
    assert np.all(np.diff(mix) >= 0.0)  # monotone merge
    assert not np.array_equal(mix, one)
    # deterministic per (seed, tenants)
    np.testing.assert_array_equal(
        mix, make_arrivals("bursty", 400, 4.0, seed=5, tenants=4))
    # aggregate keeps roughly the single-stream mean rate
    assert mix[-1] == pytest.approx(one[-1], rel=0.75)
    # tenant mixing works for poisson streams too
    p = make_arrivals("poisson", 100, 2.0, seed=1, tenants=3)
    assert p.shape == (100,) and np.all(np.diff(p) >= 0.0)
