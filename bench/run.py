#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Run from the root of a checkout.  The cell's entry in
``BENCHMARK.json`` names its configuration (``bench/configs/``) and
traffic (``bench/traffic/``).  A run:

1. makes the TUNER table on the device from the seed (``bench/data.py``);
2. runs every program shape the window will use (``bench/warmup.py``),
   then the traffic's first ``warmup_statements`` statements through
   the system, untimed;
3. measures for ``--seconds``: an open loop of arrivals at the
   traffic's fixed rate, or a closed loop of clients
   (``bench/driver.py``), each issuing calls until the time is up and
   then waiting for the last; with ``--trace 1`` under the JAX
   profiler;
4. reads the peak device memory, frees the system's state, and replays
   every statement it served (warm-up and window) on the plain numpy
   reference (``bench/replay.py``): ``correct`` holds when every
   statement's SUM, COUNT and rows modified are equal.

Lines before the last are notes.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number beside its limit.  The checks are also
the last lines of standard error.  The run exits non-zero and prints no
result when JAX finds no TPU or fewer chips than the cell asks for, or
when the program under test (``src/repro``) is not in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoChip(Exception):
    pass


def note(**kv) -> None:
    print("note " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def _compile_cache(jax) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else at the fixed ``.jax_cache/`` of the checkout; every program is
    kept, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Run:
    """What the end-to-end readers see."""

    def __init__(self, record, seconds: float, setup_s: float):
        self.record = record
        self.seconds = seconds
        self.setup_s = setup_s

    def latencies_ms(self):
        return [1e3 * (s.done - s.due) for s in self.record.served]


class TraceContext:
    """What the per-layer readers see: the trace of the window, the
    window's statements and the cell's shapes."""

    def __init__(self, trace, record, compiles_in_window, geometry, peaks):
        from bench import trace as tr
        from bench.driver import SPAN_WINDOW

        self.trace = trace
        self.record = record
        self.compiles_in_window = compiles_in_window
        self.geometry = geometry
        self.peaks = peaks
        self.window = trace.window(SPAN_WINDOW)
        if self.window is None:
            raise RuntimeError("the trace holds no window span")
        self.window_s = self.window[1] - self.window[0]
        self._runs = trace.device_runs(self.window)
        per_device = [
            tr.union((max(s, self.window[0]), min(e, self.window[1]))
                     for _, s, e in runs
                     if e > self.window[0] and s < self.window[1])
            for runs in trace.programs.values()
        ]
        self.n_devices = max(len(per_device), 1)
        self.busy_s = sum(tr.length(u) for u in per_device) / self.n_devices
        self.busy = tr.union(iv for u in per_device for iv in u)

    def device_runs(self):
        return self._runs

    def window_served(self):
        return self.record.served

    def window_table_groups(self):
        return self.record.table_groups


def breakdown(ctx, top: int = 10) -> dict:
    from bench import trace as tr

    by_program: dict = {}
    for name, s, e in ctx.device_runs():
        by_program[name] = by_program.get(name, 0.0) + (e - s) / ctx.n_devices
    ops = sorted(by_program.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(tr.gaps(ctx.busy, ctx.window),
                  key=lambda g: g[0] - g[1])[:top]
    named = []
    for s, e in gaps:
        host = tr.innermost(ctx.trace.host, 0.5 * (s + e)) or "no host span"
        named.append([host, e - s])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, config_override=None,
             traffic_override=None, peaks_override=None, control=False,
             root: Path = ROOT):
    """One run of a cell; returns the result object.  The overrides let
    a test run a cell at a tiny size and off the chip (they update the
    loaded files, and stand in for the table of peaks).  ``control``
    also replays the control (float32 sums, ``bench/replay.py``) and
    reports under ``control`` how many of its statements the
    comparison finds differing; the benchmark's own runs leave it
    off."""
    if not (root / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no program under test at {root}/src/repro")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))

    from bench import spec

    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, workload)
    cfg = spec.config(bench, cell["config"], root)
    cfg.update(config_override or {})
    traffic = spec.traffic(cell["traffic"], root)
    traffic.update(traffic_override or {})

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < int(cell["chips"]):
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX finds "
                     f"{len(devices)}")
    note(compile_cache=_compile_cache(jax))

    import numpy as np

    from bench import compiles, data, replay, warmup
    from bench.driver import Driver, Record
    from bench.peaks import peaks as chip_peaks
    from bench.workload import StatementStream, arrivals
    from repro import api
    from repro.core.table import Table

    device = devices[0]
    peaks = peaks_override or (
        chip_peaks(device.device_kind) if trace else None)
    clog = compiles.listen()

    # -- data -------------------------------------------------------------
    t = time.perf_counter()
    geom = data.table_geometry(cfg)
    arrays = data.make_table(cfg, seed)
    arrays[0].block_until_ready()
    qs = data.quantiles(arrays[0], geom["n_rows"])
    data_s = time.perf_counter() - t
    name = cfg["table"]
    tables = {name: Table(*arrays)}
    n_attrs = int(cfg["n_attrs"])
    note(table=name, rows=geom["n_rows"], pages=geom["n_pages"],
         columns=geom["n_cols"], logical_bytes=geom["logical_bytes"],
         data_s=data_s)

    # -- warm-up ----------------------------------------------------------
    tcfg = cfg["tuner"]
    build_pages = int(tcfg.get("max_build_pages_per_cycle", 64))
    t = time.perf_counter()
    c0 = clog.snapshot()

    def make_tuner(db):
        return api.PredictiveTuner(db, api.TunerConfig(
            storage_budget_bytes=float(tcfg["storage_budget_bytes"]),
            candidate_min_count=int(tcfg["candidate_min_count"])))

    shapes = warmup.warm(api, tables, traffic, n_attrs, qs, seed,
                         build_pages, make_tuner)
    gc.collect()
    db = api.Database(dict(tables))
    tuner = make_tuner(db)
    driver = Driver(db, tuner, float(tcfg["tuning_interval_ms"]))
    stream = StatementStream(api.Query, traffic, n_attrs, qs, seed)
    loop = traffic["loop"]
    burst_max = int(loop.get("burst_max", loop.get("clients", 1)))
    warm_rec = Record()
    driver.warm(stream.take(int(traffic["warmup_statements"])), burst_max,
                warm_rec)
    if loop["kind"] == "open":
        n = max(1, int(round(float(loop["rate_per_s"]) * seconds)))
        window_stmts = stream.take(n)
        due = arrivals(loop, n, seed)
    warm_s = time.perf_counter() - t
    c1 = clog.snapshot()
    note(warmup_shape_statements=shapes,
         warmup_statements=len(warm_rec.served), warmup_s=warm_s,
         compiles_in_setup=c1["count"] - c0["count"],
         compile_or_load_s=c1["seconds"] - c0["seconds"],
         cache_loads=c1["cache_loads"] - c0["cache_loads"],
         trace_and_lower_s=c1["lower_seconds"] - c0["lower_seconds"],
         cycles_in_warmup=driver.cycles)

    # -- window -----------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        from bench import trace as tr

        tr.start(trace_dir)
    cycles0 = driver.cycles
    c0 = clog.snapshot()
    window_start = time.perf_counter()
    setup_s = window_start - T_START
    if loop["kind"] == "open":
        rec = driver.open_loop(window_stmts, due, burst_max, seconds)
    else:
        rec = driver.closed_loop(stream, int(loop["clients"]), seconds)
    attempted = len(rec.served)
    c1 = clog.snapshot()
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = c1["count"] - c0["count"]
    if compiles_in_window:
        note(compiled_in_window=",".join(clog.names[c0["count"]:]))
    used_index = sum(1 for s in rec.served if s.used_index)
    n_updates = sum(1 for s in rec.served if s.query.kind == "update")
    sim = [s.sim_latency_ms for s in rec.served]
    note(window_statements=len(rec.served), window_wall_s=rec.window_s,
         index_scans=used_index, updates=n_updates,
         cycles_in_window=driver.cycles - cycles0,
         compiles_in_window=compiles_in_window,
         indexes=",".join(sorted(db.indexes)) or "-",
         simulated_latency_ms_mean=(sum(sim) / len(sim) if sim else 0.0))

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[: int(cell["chips"])])

    # -- the reference, once the system's state is freed ------------------
    served = warm_rec.served + rec.served
    del db, tuner, driver, tables
    gc.collect()
    t = time.perf_counter()
    queries = [s.query for s in served]
    planes = data.read_planes(arrays[0], replay.columns_read(queries))
    del arrays
    gc.collect()
    ref = replay.NumpyReplay(
        planes, geom["n_rows"], int(cfg["update_cap"]),
        read_only=all(q.kind == "scan" for q in queries))
    want = ref.run_all(queries)
    got = [s.result for s in served]
    differing = sum(1 for g, w in zip(got, want) if g != w)
    missing = len(want) - len(got)
    note(reference_s=time.perf_counter() - t, statements_compared=len(got))
    if differing:
        i = next(k for k, (g, w) in enumerate(zip(got, want)) if g != w)
        note(first_difference=i, got=got[i], want=want[i])
    control_differing = None
    if control:
        ctl = replay.NumpyReplay(
            planes, geom["n_rows"], int(cfg["update_cap"]),
            read_only=ref.read_only, sum_dtype=np.float32).run_all(queries)
        control_differing = sum(1 for c, w in zip(ctl, want) if c != w)
    checks = {
        "statements_differing": {"value": differing, "limit": 0},
        "statements_missing": {"value": missing, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # -- metrics ----------------------------------------------------------
    run = Run(rec, float(seconds), setup_s)
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "metrics": {}, "device": {
                  "platform": device.platform,
                  "kind": device.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": int(peak),
              }}
    if not trace:
        for m in spec.end_to_end(bench, workload):
            v = spec.reader("end_to_end", m["name"], root).read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = TraceContext(tr.load(tr.find(trace_dir)), rec,
                           compiles_in_window, geom, peaks)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in spec.per_layer(bench, workload):
            v = spec.reader("metrics", m["name"], root).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = ctx.busy_s
        result["device"]["window_s"] = ctx.window_s
        result["breakdown"] = breakdown(ctx)
    if control:
        result["control"] = {"statements_differing": control_differing,
                             "statements_compared": len(want)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (NoChip, FileNotFoundError) as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k}={c['value']} limit={c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
