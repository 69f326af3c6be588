"""The engine's own spans and host reads, read back from a profiler trace.

A tiny database runs under ``jax.profiler``: one read burst with a
table-plan group and an index-plan group, one UPDATE and one tuning
cycle that builds.  The ``.xplane.pb`` it writes is read with
``ProfileData``, as ``bench/trace.py`` reads the chip's.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench_db import QueryGen, make_tuner_db
from repro.core import Database, IndexDescriptor
from repro.core.tuner import PredictiveTuner, TunerConfig
from repro.obs import host_read, span

SPANS = (
    "repro.execute_batch",
    "repro.execute",
    "repro.executor.dispatch",
    "repro.sync",
    "repro.tuner.decide",
    "repro.build.apply",
)


def _program_spans(trace_dir):
    """(name, start_ns, end_ns, args) of every ``repro.`` host event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    args = {k: v for k, v in ev.stats}
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, args))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    src = make_tuner_db(n_rows=4_000, page_size=128)
    db = Database(dict(src.tables))
    bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
    db.vap_build_step(bi, pages=10)
    tuner = PredictiveTuner(
        db, TunerConfig(storage_budget_bytes=1e9, candidate_min_count=1)
    )
    gen = QueryGen(src, selectivity=0.01, seed=5)
    burst = [gen.low_s(attr=1, pos=0.0), gen.low_s(attr=1, pos=0.0),
             gen.low_s(attr=3), gen.low_s(attr=3), gen.low_s(attr=3)]
    update = gen.low_u(attr=3)
    # Compile outside the trace, so that the traced calls are short.
    warm = Database(dict(src.tables))
    wb = warm.create_index(IndexDescriptor("narrow", (1,)), "vap")
    warm.vap_build_step(wb, pages=10)
    warm.execute_batch(burst)
    warm.execute(update)

    trace_dir = str(tmp_path_factory.mktemp("spans"))
    jax.profiler.start_trace(trace_dir)
    try:
        stats = db.execute_batch(burst)
        db.execute(update)
        before = {n: int(b.vap.built_pages) for n, b in db.indexes.items()}
        tuner.tuning_cycle()
    finally:
        jax.profiler.stop_trace()
    after = {n: int(b.vap.built_pages) for n, b in db.indexes.items()}
    return dict(db=db, stats=stats, burst=burst, before=before,
                after=after, spans=_program_spans(trace_dir))


def _named(traced, name):
    return [s for s in traced["spans"] if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("name", SPANS)
def test_span_is_recorded(traced, name):
    assert _named(traced, name), f"no {name} span in the trace"


def test_burst_has_a_table_and_an_index_group(traced):
    paths = {s[3]["path"] for s in _named(traced, "repro.executor.dispatch")}
    assert {"table", "hybrid", "update"} <= paths
    assert any(st.used_index for st in traced["stats"])
    assert not all(st.used_index for st in traced["stats"])


def test_dispatch_and_sync_nest_inside_the_statement_call(traced):
    (batch,) = _named(traced, "repro.execute_batch")
    (single,) = _named(traced, "repro.execute")
    assert batch[3]["statements"] == len(traced["burst"])
    assert single[3]["kind"] == "update"
    roots = (batch, single)
    for name in ("repro.executor.dispatch", "repro.sync"):
        inside = [s for s in _named(traced, name)
                  if any(_inside(s, r) for r in roots)]
        assert any(_inside(s, batch) for s in inside), name
        assert any(_inside(s, single) for s in inside), name
    for s in _named(traced, "repro.executor.dispatch"):
        assert any(_inside(s, r) for r in roots)


def test_dispatch_queries_sum_to_the_burst(traced):
    (batch,) = _named(traced, "repro.execute_batch")
    spans = [s for s in _named(traced, "repro.executor.dispatch")
             if _inside(s, batch)]
    assert sum(s[3]["queries"] for s in spans) == len(traced["burst"])
    assert all(s[3]["tier"] == "single" for s in spans)


def test_pages_built_is_the_watermark_advance(traced):
    applied = _named(traced, "repro.build.apply")
    got = {}
    for s in applied:
        got[s[3]["index"]] = got.get(s[3]["index"], 0) + s[3]["pages_built"]
    want = {n: a - traced["before"].get(n, 0)
            for n, a in traced["after"].items()}
    assert sum(want.values()) > 0
    assert got == {n: v for n, v in want.items() if n in got}
    assert all(v == 0 for n, v in want.items() if n not in got)


def test_decide_counts(traced):
    (decide,) = _named(traced, "repro.tuner.decide")
    applied = _named(traced, "repro.build.apply")
    assert decide[3]["quanta"] == len(applied) > 0
    assert all(decide[2] <= s[1] for s in applied)
    assert decide[3]["candidates"] >= 2
    syncs = [s for s in _named(traced, "repro.sync") if _inside(s, decide)]
    assert {s[3]["site"] for s in syncs} >= {
        "tuner.decide.n_rows", "tuner.decide.forecast"}


def test_index_arguments_name_catalog_entries(traced):
    # the profiler drops an empty argument: table plans carry no index
    names = [s[3]["index"] for s in _named(traced, "repro.executor.dispatch")
             if s[3].get("index")]
    names += [s[3]["index"] for s in _named(traced, "repro.build.apply")]
    assert names and set(names) <= set(traced["db"].indexes)


@pytest.mark.parametrize(
    "make, read",
    [
        (lambda: jnp.asarray(7, jnp.int32), int),
        (lambda: jnp.arange(5, dtype=jnp.int32) * 3, np.asarray),
        (lambda: jnp.asarray(2.5, jnp.float32), float),
        (lambda: 11, int),
        (lambda: np.arange(4), np.asarray),
    ],
    ids=["device-scalar", "device-array", "device-float", "host-int",
         "host-array"],
)
def test_host_read_returns_what_the_plain_read_did(make, read):
    x = make()
    got, want = read(host_read(x, "test")), read(x)
    np.testing.assert_array_equal(got, want)
    assert type(got) is type(want)


def test_span_without_a_trace_is_inert():
    with span("repro.test", n=1) as sp:
        sp.set_metadata(m=2)
