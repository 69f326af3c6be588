#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s>
        --rates 1,2,3,4

One process: the table and the warm-up are made once, then each rate
runs on a fresh ``Database`` and tuner over the same loaded table: the
traffic's warm-up statements, then an open loop of ``--seconds`` at
that rate.  For each rate it prints the latency median and 90th
percentile, the statements completed per second and those still
queued at the close, and the median latency of the last third of the
completed statements over the first third: a backlog that grows
through the window shows as a ratio well over 1.  The benchmark's runs
do not use this; it sets the rate that a traffic file states once,
when the cell is defined.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import numpy as np

    from bench import data, spec, warmup
    from bench.driver import Driver, Record
    from bench.run import _compile_cache
    from bench.workload import StatementStream, arrivals
    from repro import api
    from repro.core.table import Table

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX finds no TPU", file=sys.stderr)
        return 3
    _compile_cache(jax)
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    loop = traffic["loop"]
    tcfg = cfg["tuner"]
    arrays = data.make_table(cfg, args.seed)
    geom = data.table_geometry(cfg)
    qs = data.quantiles(arrays[0], geom["n_rows"])
    tables = {cfg["table"]: Table(*arrays)}
    n_attrs = int(cfg["n_attrs"])

    def make_tuner(db):
        return api.PredictiveTuner(db, api.TunerConfig(
            storage_budget_bytes=float(tcfg["storage_budget_bytes"]),
            candidate_min_count=int(tcfg["candidate_min_count"])))

    warmup.warm(api, tables, traffic, n_attrs, qs, args.seed,
                int(tcfg["max_build_pages_per_cycle"]), make_tuner)
    for rate in (float(r) for r in args.rates.split(",")):
        gc.collect()
        db = api.Database(dict(tables))
        driver = Driver(db, make_tuner(db), float(tcfg["tuning_interval_ms"]))
        stream = StatementStream(api.Query, traffic, n_attrs, qs, args.seed)
        driver.warm(stream.take(int(traffic["warmup_statements"])),
                    int(loop["burst_max"]), Record())
        n = max(1, int(round(rate * args.seconds)))
        stmts = stream.take(n)
        due = arrivals(dict(loop, rate_per_s=rate), n, args.seed)
        t = time.perf_counter()
        rec = driver.open_loop(stmts, due, int(loop["burst_max"]),
                               args.seconds)
        lat = np.asarray([1e3 * (s.done - s.due) for s in rec.served])
        third = max(1, len(lat) // 3)
        print(json.dumps({
            "rate_per_s": rate,
            "offered": n,
            "completed": len(lat),
            "p50_ms": float(np.percentile(lat, 50)),
            "p90_ms": float(np.percentile(lat, 90)),
            "queued_at_close": n - len(lat),
            "growth": float(np.median(lat[-third:]) / np.median(lat[:third])),
            "completed_per_s": len(lat) / rec.window_s,
            "wall_s": time.perf_counter() - t,
        }), flush=True)
        del db, driver, stream
    return 0


if __name__ == "__main__":
    sys.exit(main())
