"""``tuner_wide.balanced`` at a tiny size on the CPU, and the MVCC
write path's readers on the recorded window.

The cell runs with its own traffic override (none: the committed
traffic at the tiny row count of ``conftest.tiny_config``).
"""

import math
from types import SimpleNamespace

import pytest

from bench import spec, trace
from bench.run import TraceContext
from bench.tests.conftest import FAKE_PEAKS, tiny_config
from bench.tests.test_metrics import DATA, load_record

CELL = "tuner_wide.balanced"
TRAFFIC = {}
WRITE_METRICS = ("update_ms_per_stmt", "update_roofline",
                 "table_copies_in_window")


@pytest.mark.parametrize("trace_on", [False, True], ids=["trace0", "trace1"])
def test_balanced_runs_and_agrees_with_reference(trace_on):
    from bench.run import run_cell

    r = run_cell(CELL, 2**33 + 5, 3.0, trace_on, require_tpu=False,
                 config_override=tiny_config(), traffic_override=TRAFFIC,
                 peaks_override=FAKE_PEAKS)
    assert r["correct"], r["checks"]
    assert r["checks"]["statements_missing"] == {"value": 0, "limit": 0}
    assert r["attempted"] > 0 and r["failed"] == 0
    bench = spec.load_benchmark()
    wanted = (spec.per_layer(bench, CELL) if trace_on
              else spec.end_to_end(bench, CELL))
    assert set(r["metrics"]) == {m["name"] for m in wanted}
    if trace_on:
        m = r["metrics"]
        assert set(WRITE_METRICS) <= set(m)
        assert m["compiles_in_window"]["value"] == 0.0
        assert m["table_copies_in_window"]["value"] == 0.0
        assert m["update_ms_per_stmt"]["value"] > 0
        assert m["update_roofline"]["value"] > 0
        ops = dict(r["breakdown"]["device_ops"])
        assert "update_rows" in ops and "copy_table" not in ops


# The recorded window: TUNER narrow at 20,000 rows (conftest.TINY_ROWS),
# 21 columns, 118 pages of 256 rows.
GEOMETRY = {"n_rows": 20_000, "page_size": 256, "n_cols": 21,
            "capacity": 118 * 256}


@pytest.fixture(scope="module")
def ctx():
    tr = trace.load(str(DATA / "window.xplane.pb"))
    return TraceContext(tr, load_record(), 0, GEOMETRY,
                        {"hbm_bytes_per_s": 1e10})


def test_update_time_per_update(ctx):
    mod = spec.reader("metrics", "update_ms_per_stmt")
    updates = [s for s in ctx.window_served() if s.query.kind == "update"]
    runs = [r for r in ctx.device_runs() if r[0] in mod.PROGRAMS]
    assert updates and runs
    want = 1e3 * sum(e - s for _, s, e in runs) / len(updates)
    assert mod.read(ctx) == pytest.approx(want)


def test_update_roofline_bytes_from_shapes(ctx):
    mod = spec.reader("metrics", "update_roofline")
    n_rows, want = 20_000, 0
    for s in ctx.window_served():
        if s.query.kind != "update":
            continue
        planes = len(set(s.query.attrs)) + 2
        want += planes * math.ceil(n_rows / 256) * 256 * 4
        m = s.result[2]
        want += m * 23 * 4 + m * 4
        n_rows += m
    assert want > 0 and mod.least_bytes(ctx) == want
    assert 0 < mod.read(ctx) <= 100


def test_update_roofline_counts_appended_rows():
    mod = spec.reader("metrics", "update_roofline")
    q = SimpleNamespace(kind="update", attrs=(1, 2))
    served = [SimpleNamespace(query=q, result=(0, 0, 256))] * 2
    c = SimpleNamespace(geometry={"n_rows": 256, "page_size": 256,
                                  "n_cols": 3, "capacity": 1024},
                        window_served=lambda: served)
    # First: 1 page of 4 planes; second: 2 pages; each writes 256 rows
    # of 5 planes and terminates 256.
    assert mod.least_bytes(c) == (4 * 256 * 4 + 256 * 24) + (
        4 * 512 * 4 + 256 * 24)


def test_copies_need_program_spans(ctx):
    mod = spec.reader("metrics", "table_copies_in_window")
    # The recording predates the program's spans: nothing to read.
    assert mod.read(ctx) is None
    spans = trace.Trace(host=[("bench.window", 0.0, 10.0),
                              ("repro.execute", 1.0, 2.0),
                              ("repro.table.own", 1.2, 1.5),
                              ("repro.table.own", 11.0, 12.0)])
    c = TraceContext(spans, load_record(), 0, GEOMETRY,
                     {"hbm_bytes_per_s": 1e10})
    assert mod.read(c) == 1.0
