"""Each cell end to end at a tiny row count on the CPU."""

import json

import pytest

from bench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_agrees_with_reference(run_tiny, cell, trace):
    r = run_tiny(cell, 2**33 + 7, trace=trace)
    assert r["correct"], r["checks"]
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["checks"]["statements_differing"] == {"value": 0, "limit": 0}
    assert r["attempted"] > 0 and r["failed"] == 0
    bench = spec.load_benchmark()
    wanted = spec.per_layer(bench, cell) if trace else spec.end_to_end(
        bench, cell)
    assert set(r["metrics"]) == {m["name"] for m in wanted}
    for m in wanted:
        assert r["metrics"][m["name"]]["unit"] == m["unit"]
    if trace:
        dev = r["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(r["breakdown"]["device_ops"]) <= 10
        compiles = [v for k, v in r["metrics"].items()
                    if k.startswith("compiles_in_window")]
        assert compiles == [{"value": 0.0, "unit": "compiles"}]
    json.dumps(r)  # the result line is plain JSON


def test_same_seed_same_data():
    from bench import data
    from bench.tests.conftest import tiny_config

    cfg = spec.config(spec.load_benchmark(), "tuner_narrow")
    cfg.update(tiny_config())
    a = data.make_table(cfg, 2**33 + 7)
    b = data.make_table(cfg, 2**33 + 7)
    c = data.make_table(cfg, 2**33 + 8)
    assert all(bool((x == y).all()) for x, y in zip(a, b))
    assert not bool((a[0] == c[0]).all())
