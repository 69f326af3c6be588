#!/usr/bin/env python3
"""Chip smoke test: the predictive-indexing engine's main path on a TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --chips 4     # the shard_map mesh path, 4 chips

One chip: TUNER narrow at the paper's scale (Section V: 10M rows, 20
Zipf attributes plus the a_0 timestamp, 256-row pages, headroom 1.5,
seed 7) is loaded through ``repro.api.make_tuner_db``.  A read-heavy
``hybrid_workload`` (90% LOW-S scans, 10% LOW-U/HIGH-U updates, 200
statements in two phases of 100 whose hot attribute shifts) then runs
through ``run_workload`` in read bursts of 8 with the predictive tuner
on, twice: once on the default jnp tier and once with
``ExecOptions.use_kernel`` (the compiled Pallas scan kernels).  Both
passes must give identical per-statement results, the kernel pass must
report the kernel tier, the tuner must have built index pages that
scans used, and every statement must agree with a plain numpy replay of
the workload (predicate, MVCC snapshot ``begin_ts <= ts < end_ts``,
SUM and COUNT with the engine's int32 wraparound, and the MVCC UPDATE's
row count).

Four chips: the same table split into 4 round-robin shards with
``ExecOptions(mesh=True)`` (a placement failure raises), 40 read-only
statements in bursts of 8 with the tuner on, checked against the numpy
replay at ``--rows`` and against the single-device stacked dispatch
(``mesh=False``) at ``--compare-rows``.

Lines before the last are notes, not metrics.  The last line of
standard output is one JSON object naming the device.  The script exits
non-zero and prints no result when JAX finds no TPU, when the
repository's ``src/`` is not beside it, or when any check fails.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
PAPER_ROWS = 10_000_000
SEED = 7
STATEMENTS = 200
MESH_STATEMENTS = 40
BATCH = 8
I32_MAX = 2**31 - 1


def note(**kv) -> None:
    print("note " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Plain numpy reference: replays the workload statement by statement
# ---------------------------------------------------------------------------


class NumpyReplay:
    """Host-side MVCC column store, independent of the engine.

    Statement ``i`` runs at logical snapshot ``ts = i + 1``; loaded rows
    begin at 0.  The engine's snapshots follow a clock that never goes
    back, and a burst holds only scans, so the same order of statements
    gives the same visible versions.  An UPDATE terminates the first
    ``update_cap`` visible matches in rid order and appends their new
    versions at the append watermark, as ``core.table.update_rows``
    documents.
    """

    def __init__(self, table, update_cap: int):
        n_pages, page_size, n_attrs = table.data.shape
        cap = n_pages * page_size
        data = np.asarray(table.data).reshape(cap, n_attrs)
        # Writable copies: a host view of a device array is read-only.
        self.cols = np.array(data.T, order="C")  # (n_attrs, capacity)
        self.begin = np.array(table.begin_ts).reshape(cap)
        self.end = np.array(table.end_ts).reshape(cap)
        self.n_rows = int(table.n_rows)
        self.capacity = cap
        self.update_cap = update_cap

    def _match(self, q, ts: int) -> np.ndarray:
        mask = (self.begin <= ts) & (ts < self.end)
        for a, lo, hi in zip(q.attrs, q.los, q.his):
            col = self.cols[a]
            mask &= (col >= lo) & (col <= hi)
        return mask

    def run(self, stmt: int, q):
        """(agg_sum, count, rows_modified), as ``RunResult.results``."""
        ts = stmt + 1
        mask = self._match(q, ts)
        if q.kind == "scan":
            total = int(self.cols[q.agg_attr][mask].sum(dtype=np.int64))
            wrapped = int(np.array(total, np.int64).astype(np.int32))
            return (wrapped, int(mask.sum()), 0)
        if q.kind != "update":
            raise SmokeFailure(f"numpy replay has no {q.kind!r} statement")
        rids = np.flatnonzero(mask)[: self.update_cap]
        n = len(rids)
        self.end[rids] = ts
        new = np.arange(self.n_rows, self.n_rows + n)
        keep = new < self.capacity
        rows = self.cols[:, rids[keep]].copy()
        rows[list(q.set_attrs), :] = np.asarray(q.set_vals)[:, None]
        self.cols[:, new[keep]] = rows
        self.begin[new[keep]] = ts
        self.end[new[keep]] = I32_MAX
        self.n_rows = min(self.n_rows + n, self.capacity)
        return (0, 0, n)


def numpy_results(table, workload, update_cap: int):
    ref = NumpyReplay(table, update_cap)
    return [ref.run(i, q) for i, (_, q) in enumerate(workload)]


# ---------------------------------------------------------------------------
# Engine passes, through the public entry points
# ---------------------------------------------------------------------------


def run_pass(api, tables, workload, execution, compile_clock):
    """One ``run_workload`` over a fresh Database on ``tables``.

    Tuning cycles fire every 10 simulated seconds at the paper's 10M
    rows (the paper's SLOW frequency, Section V-B), scaled with the row
    count so a cut table still tunes as often per statement.  Each
    build quantum merges its pages into the index in linear passes over
    its capacity, so the cycle count bounds the pass's time."""
    db = api.Database(dict(tables), num_shards=execution.num_shards)
    rows = int(db.tables["narrow"].n_rows)
    tuner = api.PredictiveTuner(
        db,
        api.TunerConfig(storage_budget_bytes=1e9, candidate_min_count=2),
    )
    cfg = api.RunConfig(
        execution=execution,
        tuning=api.TuningOptions(
            tuning_interval_ms=10_000.0 * rows / PAPER_ROWS
        ),
    )
    c0 = compile_clock[0]
    t0 = time.perf_counter()
    note(pass_start=execution)
    res = api.run_workload(db, tuner, workload, cfg)
    wall = time.perf_counter() - t0
    index_hits = sum(r.used_index for r in db.monitor.records)
    built = max(res.built_fraction, default=0.0)
    out = dict(
        results=list(res.results),
        tiers=dict(res.execution_tiers),
        wall_s=wall,
        compile_s=compile_clock[0] - c0,
        built_fraction=built,
        tuner_work_units=res.tuner_work_units,
        index_hits=index_hits,
        indexes=sorted(db.indexes),
    )
    del db, tuner, res
    gc.collect()
    return out


def report_pass(name, out, n_statements):
    note(
        phase=name,
        statements=n_statements,
        wall_s=out["wall_s"],
        compile_s=out["compile_s"],
        execution_tiers=json.dumps(out["tiers"], sort_keys=True),
        max_built_fraction=out["built_fraction"],
        tuner_work_units=out["tuner_work_units"],
        index_scans_in_window=out["index_hits"],
        indexes=",".join(out["indexes"]) or "-",
    )


def agree(name, got, want):
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    check(len(got) == len(want), f"{name}: {len(got)} vs {len(want)} rows")
    if bad:
        i = bad[0]
        raise SmokeFailure(
            f"{name}: {len(bad)} statements differ; first #{i}: "
            f"{got[i]} vs {want[i]}"
        )
    note(check=name, statements=len(got), identical=True)


def hbm_note(device, label):
    stats = device.memory_stats() or {}
    note(
        hbm=label,
        device_id=device.id,
        bytes_in_use=stats.get("bytes_in_use", "not reported"),
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        bytes_limit=stats.get("bytes_limit", "not reported"),
    )
    return stats


def load(api, rows):
    t0 = time.perf_counter()
    src = api.make_tuner_db(
        n_rows=rows, page_size=256, narrow_attrs=20, headroom=1.5, seed=SEED
    )
    t = src.tables["narrow"]
    t.data.block_until_ready()
    logical = sum(int(np.prod(x.shape)) * 4 for x in t[:3])
    note(
        load="tuner_narrow",
        rows=rows,
        pages=t.n_pages,
        page_size=t.page_size,
        attrs=t.n_attrs,
        logical_bytes=logical,
        seconds=time.perf_counter() - t0,
    )
    return src, logical


def one_chip(api, jax, args, compile_clock):
    device = jax.devices()[0]
    base = (device.memory_stats() or {}).get("bytes_in_use", 0)
    src, logical = load(api, args.rows)
    stats = hbm_note(device, "after_load")
    if "bytes_in_use" in stats:
        note(table_hbm_over_logical=(stats["bytes_in_use"] - base) / logical)
    gen = api.QueryGen(src, selectivity=0.01)
    workload = api.hybrid_workload(gen, "read_heavy", total=STATEMENTS)
    kinds = [q.kind for _, q in workload]
    note(
        workload="hybrid_read_heavy",
        statements=len(kinds),
        phases=len({phase for phase, _ in workload}),
        scans=kinds.count("scan"),
        updates=kinds.count("update"),
        scans_before_first_write=kinds.index("update")
        if "update" in kinds
        else len(kinds),
    )

    t0 = time.perf_counter()
    want = numpy_results(src.tables["narrow"], workload, update_cap=512)
    note(numpy_replay_s=time.perf_counter() - t0)

    passes = {}
    for name, use_kernel in (("jnp", False), ("kernel", True)):
        execution = api.ExecOptions(
            read_batch_size=BATCH, num_shards=1, use_kernel=use_kernel
        )
        out = run_pass(api, src.tables, workload, execution, compile_clock)
        report_pass(name, out, len(kinds))
        passes[name] = out

    check(
        passes["kernel"]["tiers"].get("kernel", 0) > 0,
        "the kernel pass never used the kernel tier",
    )
    check(
        "kernel" not in passes["jnp"]["tiers"],
        "the jnp pass used the kernel tier",
    )
    for name, out in passes.items():
        check(out["built_fraction"] > 0, f"{name}: no index pages built")
        check(out["index_hits"] > 0, f"{name}: no scan used an index")
    agree("kernel_vs_jnp", passes["kernel"]["results"],
          passes["jnp"]["results"])
    agree("jnp_vs_numpy", passes["jnp"]["results"], want)
    hbm_note(device, "end")


def placement(tables):
    """Which devices hold the sharded table and its stacked copy."""
    from repro.core.table import stacked_shards

    st = tables["narrow"]
    stk = stacked_shards(st)
    shard_devs = sorted({d.id for t in st.shards for d in t.data.devices()})
    stack_devs = sorted(stk.table.data.sharding.device_set, key=lambda d: d.id)
    return shard_devs, [d.id for d in stack_devs]


def four_chips(api, jax, args, compile_clock):
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, has {len(devices)}")

    def mesh_run(rows, runs):
        src, _ = load(api, rows)
        gen = api.QueryGen(src, selectivity=0.01)
        workload = api.hybrid_workload(gen, "read_only", total=MESH_STATEMENTS)
        table = src.tables["narrow"]
        outs = {}
        for name, mesh, use_kernel in runs:
            execution = api.ExecOptions(
                read_batch_size=BATCH,
                num_shards=4,
                mesh=mesh,
                use_kernel=use_kernel,
            )
            out = run_pass(api, src.tables, workload, execution,
                           compile_clock)
            report_pass(f"{name}@{rows}", out, len(workload))
            outs[name] = out
        db = api.Database(dict(src.tables), num_shards=4)
        shard_devs, stack_devs = placement(db.tables)
        note(rows=rows, sharded_table_devices=shard_devs,
             stacked_copy_devices=stack_devs)
        for d in devices[:4]:
            hbm_note(d, f"mesh@{rows}")
        del db
        gc.collect()
        return outs, table, workload

    runs = [("mesh_kernel", True, True)]
    same_size = args.compare_rows == args.rows
    if same_size:
        runs.append(("stacked", False, False))
    full, table, workload = mesh_run(args.rows, runs)
    check(set(full["mesh_kernel"]["tiers"]) == {"shard_map"},
          f"mesh pass tiers {full['mesh_kernel']['tiers']}")
    check(full["mesh_kernel"]["index_hits"] > 0,
          "mesh pass: no scan used an index")
    want = numpy_results(table, workload, update_cap=512)
    agree("mesh_vs_numpy", full["mesh_kernel"]["results"], want)
    del table
    gc.collect()
    if not same_size:
        full, _, _ = mesh_run(
            args.compare_rows,
            [("mesh_kernel", True, True), ("stacked", False, False)],
        )
    check(set(full["stacked"]["tiers"]) == {"vmap-stacked"},
          f"stacked pass tiers {full['stacked']['tiers']}")
    agree("mesh_vs_stacked", full["mesh_kernel"]["results"],
          full["stacked"]["results"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--rows", type=int, default=PAPER_ROWS)
    p.add_argument("--compare-rows", type=int, default=PAPER_ROWS)
    args = p.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro import api
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: JAX finds no TPU (platform "
            f"{devices[0].platform!r}); nothing was run",
            file=sys.stderr,
        )
        return 3
    note(compile_cache=enable_compile_cache())
    if args.rows != PAPER_ROWS:
        note(size_cut=f"rows {PAPER_ROWS} -> {args.rows}")
    if args.chips == 4 and args.compare_rows != PAPER_ROWS:
        note(size_cut=f"compare rows {PAPER_ROWS} -> {args.compare_rows}")

    compile_clock = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_clock[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(api, jax, args, compile_clock)
        else:
            one_chip(api, jax, args, compile_clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    note(total_s=time.perf_counter() - t0, compile_s=compile_clock[0])
    d = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
