"""The readers of the program's own spans (``bench/program_spans.py``).

The committed recording in ``data/`` predates the program's spans, so
the window here is recorded afresh by ``record_trace.py``, unchanged,
into a temporary directory.
"""

import math

import pytest

from bench import program_spans as ps
from bench import spec, trace
from bench.run import TraceContext
from bench.tests import record_trace, test_metrics

PEAKS = {"hbm_bytes_per_s": 1e10}
PER_LAYER = [m["name"] for m in spec.load_benchmark()["per_layer"]]
SPAN_READERS = ("executor_self_ms_per_stmt", "host_sync_idle_ms_per_stmt",
                "decide_ms_per_cycle")


def _context(data_dir):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(test_metrics, "DATA", data_dir)
        rec = test_metrics.load_record()
    tr = trace.load(str(data_dir / "window.xplane.pb"))
    return TraceContext(tr, rec, 0, test_metrics.GEOMETRY, PEAKS)


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    here = tmp_path_factory.mktemp("recording")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(record_trace, "HERE", here)
        record_trace.main()
    return _context(here / "data")


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_reader_reads_a_window_with_program_spans(ctx, name):
    v = spec.reader("metrics", name).read(ctx)
    assert v is not None and math.isfinite(v) and v >= 0


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_find_nothing_without_program_spans(name):
    """The committed window was recorded from a program with no spans
    of its own: each reader of them finds nothing and says so."""
    committed = _context(test_metrics.DATA)
    assert spec.reader("metrics", name).read(committed) is None


def test_executor_self_time_lies_inside_the_outside_reading(ctx):
    inside = spec.reader("metrics", "executor_self_ms_per_stmt").read(ctx)
    outside = spec.reader("metrics", "executor_host_ms_per_stmt").read(ctx)
    assert 0 < inside < outside


def test_sync_idle_by_hand(ctx):
    syncs = sorted(ps.in_window(ctx, (ps.SYNC,)))
    assert syncs
    idle = 0.0
    for s, e in trace.union(syncs):
        busy = sum(max(0.0, min(e, b1) - max(s, b0)) for b0, b1 in ctx.busy)
        idle += (e - s) - busy
    got = spec.reader("metrics", "host_sync_idle_ms_per_stmt").read(ctx)
    assert got == pytest.approx(1e3 * idle / len(ctx.window_served()))


def test_decide_by_hand(ctx):
    spans = ctx.trace.spans(ps.DECIDE)
    assert spans and all(ctx.window[0] <= s and e <= ctx.window[1]
                         for s, e in spans)
    want = 1e3 * sum(e - s for s, e in spans) / len(spans)
    got = spec.reader("metrics", "decide_ms_per_cycle").read(ctx)
    assert got == pytest.approx(want)


def test_build_programs_run_after_an_apply_span(ctx):
    programs = spec.reader("metrics", "build_device_share").PROGRAMS
    applied = ctx.trace.spans(ps.APPLY)
    runs = [r for r in ctx.device_runs() if r[0] in programs]
    assert runs and applied
    for _, start, _ in runs:
        assert any(s <= start for s, _ in applied)
