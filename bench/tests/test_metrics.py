"""Every per-layer and end-to-end reader, on the small CPU trace in
``data/`` (``record_trace.py`` records it)."""

import json
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spec, trace
from bench.driver import Record, Served
from bench.run import Run, TraceContext, breakdown

DATA = Path(__file__).resolve().parent / "data"
GEOMETRY = {"n_rows": 20_000, "page_size": 256}


def load_record() -> Record:
    raw = json.loads((DATA / "window.json").read_text())
    rec = Record(window_s=raw["window_s"])
    for s in raw["served"]:
        q = SimpleNamespace(kind=s["kind"], attrs=tuple(s["attrs"]),
                            agg_attr=s["agg_attr"])
        rec.served.append(Served(q, s["due"], s["done"],
                                 tuple(s["result"]), s["used_index"],
                                 s["sim_latency_ms"]))
    rec.table_groups = [[(tuple(a), g) for a, g in groups]
                        for groups in raw["table_groups"]]
    return rec


@pytest.fixture(scope="module")
def ctx():
    tr = trace.load(str(DATA / "window.xplane.pb"))
    return TraceContext(tr, load_record(), 0, GEOMETRY,
                        {"hbm_bytes_per_s": 1e10})


PER_LAYER = [m["name"] for m in spec.load_benchmark()["per_layer"]]


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_reader(ctx, name):
    v = spec.reader("metrics", name).read(ctx)
    assert v is not None and math.isfinite(v) and v >= 0


def test_trace_holds_the_window_programs(ctx):
    names = {n for n, _, _ in ctx.device_runs()}
    assert {"batched_full_table_scan", "batched_hybrid_scan",
            "_build_pages_vap", "update_rows"} <= names
    assert 0 < ctx.busy_s < ctx.window_s


def test_shares_are_shares(ctx):
    for name in ("device_idle_share", "build_device_share",
                 "table_scan_roofline"):
        v = spec.reader("metrics", name).read(ctx)
        assert 0 < v < 100


def test_roofline_bytes_from_shapes(ctx):
    mod = spec.reader("metrics", "table_scan_roofline")
    groups = sum(len(g) for g in ctx.window_table_groups())
    pages = math.ceil(20_000 / 256)
    assert groups > 0
    # one predicate column, a_2, begin_ts, end_ts per group
    assert mod.least_bytes(ctx) == groups * 4 * pages * 256 * 4


def test_readers_find_nothing_in_an_empty_trace():
    empty = trace.Trace(host=[("bench.window", 0.0, 1.0)])
    c = TraceContext(empty, Record(), 0, GEOMETRY,
                     {"hbm_bytes_per_s": 1e10})
    for name in PER_LAYER:
        v = spec.reader("metrics", name).read(c)
        if name.startswith("compiles_in_window"):
            assert v == 0.0
        else:
            assert v is None


def test_breakdown(ctx):
    b = breakdown(ctx)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(isinstance(n, str) for n, _ in b["idle_gaps"])


@pytest.mark.parametrize(
    "name", [m["name"] for m in spec.load_benchmark()["end_to_end"]])
def test_end_to_end_reader(name):
    rec = load_record()
    run = Run(rec, seconds=1.0, setup_s=12.5)
    v = spec.reader("end_to_end", name).read(run)
    assert v is not None and v > 0


def test_latency_tail_is_of_every_statement():
    rec = Record(served=[
        Served(None, 0.0, i / 100.0, (0, 0, 0), False, 0.0)
        for i in range(1, 101)], window_s=1.25)
    ctx = SimpleNamespace(window_served=lambda: rec.served)
    p90 = spec.reader("metrics", "stmt_p90_ms").read(ctx)
    assert p90 == pytest.approx(901.0)
    p50 = spec.reader("metrics", "stmt_p50_ms").read(ctx)
    assert p50 == pytest.approx(505.0)


def test_rate_counts_every_statement_over_the_time_to_the_last():
    rec = Record(served=[
        Served(None, 0.0, i / 100.0, (0, 0, 0), False, 0.0)
        for i in range(1, 101)], window_s=1.25)
    run = Run(rec, 1.0, 1.0)
    assert spec.reader("end_to_end", "stmts_per_s").read(run) == 80.0
