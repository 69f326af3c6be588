"""Record the small CPU trace the metric tests read.

    JAX_PLATFORMS=cpu python bench/tests/record_trace.py

Drives a tiny TUNER narrow table through ``bench.driver.Driver`` (an
open loop of the read-heavy mix, so the window holds table-plan scans,
index-plan scans, updates and build quanta) under the JAX profiler,
and writes ``data/window.xplane.pb`` and ``data/window.json`` (the
window's statements and table-plan groups, as the readers see them).
"""

import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def record_to_json(rec) -> dict:
    return {
        "window_s": rec.window_s,
        "served": [
            {"kind": s.query.kind,
             "attrs": list(s.query.attrs), "agg_attr": s.query.agg_attr,
             "due": s.due, "done": s.done, "result": list(s.result),
             "used_index": s.used_index,
             "sim_latency_ms": s.sim_latency_ms}
            for s in rec.served
        ],
        "table_groups": [[[list(a), g] for a, g in groups]
                         for groups in rec.table_groups],
    }


def main() -> None:
    from bench import data, trace, warmup
    from bench.driver import Driver, Record
    from bench.spec import load_benchmark, config, traffic
    from bench.tests.conftest import tiny_config
    from bench.workload import StatementStream, arrivals
    from repro import api
    from repro.core.table import Table

    bench = load_benchmark()
    cfg = config(bench, "tuner_narrow")
    cfg.update(tiny_config())
    tr = traffic("shift_read_heavy")
    arrays = data.make_table(cfg, 12345)
    geom = data.table_geometry(cfg)
    qs = data.quantiles(arrays[0], geom["n_rows"])
    tables = {"narrow": Table(*arrays)}

    def make_tuner(db):
        return api.PredictiveTuner(db, api.TunerConfig(
            storage_budget_bytes=1e9, candidate_min_count=2))

    warmup.warm(api, tables, tr, 20, qs, 12345, 64, make_tuner)
    db = api.Database(dict(tables))
    driver = Driver(db, make_tuner(db), cfg["tuner"]["tuning_interval_ms"])
    stream = StatementStream(api.Query, tr, 20, qs, 12345)
    stmts = stream.take(40)
    due = arrivals({"arrivals": "poisson", "rate_per_s": 40.0}, 40, 12345)
    out = tempfile.mkdtemp()
    trace.start(out)
    rec = driver.open_loop(stmts, due, 8, 60.0)
    import jax

    jax.profiler.stop_trace()
    (HERE / "data").mkdir(exist_ok=True)
    shutil.copy(trace.find(out), HERE / "data" / "window.xplane.pb")
    shutil.rmtree(out)
    with open(HERE / "data" / "window.json", "w") as f:
        json.dump(record_to_json(rec), f)


if __name__ == "__main__":
    main()
