"""Benchmark driver: one function per paper figure/table + the kernel
microbenchmark + the roofline summary.  Prints ``name,us_per_call,
derived`` CSV lines (the ``emit`` contract in common.py).

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def bench_kernels():
    """One query through the Pallas batched kernel (a batch of one) vs
    the pure-jnp reference.  On the CPU the kernel runs in interpret
    mode (the comparison point is correctness + call overhead); on a
    TPU it is the compiled kernel."""
    import jax
    import numpy as np

    from benchmarks.common import emit
    from repro.bench_db.schema import make_tuner_db
    from repro.kernels import ops
    from repro.kernels.ref import filter_agg_ref

    db = make_tuner_db(n_rows=40_000, page_size=256)
    t = db.tables["narrow"]
    lo, hi = db.quantile_bounds("narrow", 0.01, 0.3)

    def timed(fn, n=5):
        fn()  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e6

    us_ref = timed(lambda: filter_agg_ref(
        t.data[:, :, 1], t.data[:, :, 1], t.data[:, :, 2], t.begin_ts,
        t.end_ts, lo, hi, ops.I32_MIN, ops.I32_MAX, 0)[0].block_until_ready())
    us_pal = timed(lambda: ops.scan_table_batched(
        t, (1,), np.array([[lo]]), np.array([[hi]]), np.zeros(1, np.int32),
        agg_attr=2)[0].block_until_ready())
    emit("kernel.filter_agg_ref_jnp", us_ref, "pure-jnp oracle")
    if ops.interpret_mode():
        emit("kernel.filter_agg_pallas_interpret", us_pal,
             "ops.scan_table_batched at B=1, interpret=True "
             "(CPU correctness mode)")
    else:
        emit("kernel.filter_agg_pallas_compiled", us_pal,
             f"ops.scan_table_batched at B=1 on "
             f"{jax.devices()[0].device_kind}")


def bench_roofline():
    from benchmarks.common import emit
    from benchmarks import roofline
    rows = []
    try:
        rows = roofline.table(out=open("/dev/null", "w"))
    except Exception:
        pass
    if not rows:
        emit("roofline.table", 0.0, "no dryrun artifacts yet "
             "(run python -m repro.launch.dryrun --all)")
        return
    worst = min(rows, key=lambda rt: rt[1]["roofline_fraction"])
    collb = max(rows, key=lambda rt: rt[1]["collective_s"])
    for rec, t in rows:
        emit(f"roofline.{rec['arch']}.{rec['shape']}",
             t["dominant_s"] * 1e6,
             f"dom={t['dominant']} roofline={100*t['roofline_fraction']:.1f}% "
             f"useful={t['useful_ratio']:.2f} peak={t['peak_gib']:.1f}GiB")
    emit("roofline.worst_cell", worst[1]["dominant_s"] * 1e6,
         f"{worst[0]['arch']}/{worst[0]['shape']} "
         f"{100*worst[1]['roofline_fraction']:.1f}%")
    emit("roofline.most_collective_bound", collb[1]["collective_s"] * 1e6,
         f"{collb[0]['arch']}/{collb[0]['shape']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workloads (CI mode)")
    ap.add_argument("--all", action="store_true",
                    help="run every registered benchmark (the default; "
                         "spelled out for scripts)")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name (see --list)")
    ap.add_argument("--list", action="store_true",
                    help="print the registered benchmark names and exit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted records as JSON "
                         "(the nightly-CI perf artifact)")
    args = ap.parse_args()

    from benchmarks import (async_tuning, batched_scan, crack_on_scan,
                            fault_recovery, fig2_schemes,
                            fig6_decision_logic, fig7_holistic,
                            fig8_affinity, fig9_layout,
                            fig10_adaptability, mesh_scan, replica_routing, serving_slo,
                            shard_tuning, sharded_scan)
    from benchmarks import common

    quick = args.quick
    jobs = [
        ("fig2", lambda: fig2_schemes.run(
            total=600 if quick else 1500, quiet=True)),
        ("fig6", lambda: fig6_decision_logic.run(
            total=1200 if quick else 3000,
            phase_len=150 if quick else 300, quiet=True)),
        ("fig7", lambda: fig7_holistic.run(
            seg_len=150 if quick else 400, quiet=True)),
        ("fig8", lambda: fig8_affinity.run(
            total=500 if quick else 1200, quiet=True)),
        ("fig9", lambda: fig9_layout.run(
            total=250 if quick else 500, quiet=True)),
        ("fig10", lambda: fig10_adaptability.run(
            total=600 if quick else 1500, quiet=True)),
        ("batched", lambda: batched_scan.run(
            n_queries=64 if quick else 128, quiet=True)),
        ("sharded", lambda: sharded_scan.run(
            n_queries=32 if quick else 64,
            n_rows=10_000 if quick else 20_000, quiet=True)),
        ("async", lambda: async_tuning.run(
            total=400 if quick else 1200, quiet=True)),
        ("shard_tuning", lambda: shard_tuning.run(
            total=240 if quick else 360,
            phase_len=120 if quick else 180, quiet=True)),
        ("crack_on_scan", lambda: crack_on_scan.run(
            total=160 if quick else 240,
            phase_len=55 if quick else 80, quiet=True)),
        # burst size NOT reduced under --quick: the headline is burst
        # amortization of the mesh dispatch's fixed cost, which needs
        # the full burst to be meaningful (see mesh_scan docstring)
        ("mesh", lambda: mesh_scan.run(quiet=True)),
        ("serving_slo", lambda: serving_slo.run(
            total=400 if quick else 1200,
            phase_len=100 if quick else 150, quiet=True)),
        ("replica_routing", lambda: replica_routing.run(
            total=120 if quick else 240, quiet=True)),
        ("fault_recovery", lambda: fault_recovery.run(
            total=120 if quick else 240, quiet=True)),
        ("kernels", bench_kernels),
        ("roofline", bench_roofline),
    ]
    names = [name for name, _ in jobs]
    if args.list:
        print("\n".join(names))
        return
    if args.only is not None and args.only not in names:
        # A typo must not silently run *nothing* -- fail loudly with
        # the registry so scripts and CI notice.
        raise SystemExit(
            f"run.py: unknown benchmark {args.only!r}; "
            f"known benchmarks: {', '.join(names)}")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    common.reset_records()
    failures = []
    for name, fn in jobs:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)
        except Exception as e:
            failures.append(name)
            traceback.print_exc()
            print(f"{name}.FAILED,0.0,{e!r}")
    if args.json:
        import json
        import platform
        # Stable BENCH_<prnum>.json schema (benchmarks/trajectory.py
        # compares these run over run): bump "schema" only on
        # incompatible record changes.  Each record carries name, the
        # median-style latency (us_per_call / median_ms) and, where a
        # benchmark has a baseline, its headline speedup.
        payload = {
            "schema": 1,
            "created_unix_s": round(time.time(), 1),
            "argv": sys.argv[1:],
            "python": platform.python_version(),
            "failures": failures,
            "records": common.RECORDS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.RECORDS)} records to {args.json}",
              file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
