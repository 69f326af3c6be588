"""Microbenchmark: per-shard scan fan-out over sharded storage.

``Database(num_shards=S)`` partitions pages round-robin across S
shards; ``execute_batch`` then runs each plan group as ONE stacked
single dispatch for any shard count, or on a ``shard_map`` mesh when
the host's devices can place the shards.  Results are bit-identical
across shard counts (asserted here against the 1-shard engine), so
this bench isolates the *dispatch* cost of sharding: on one CPU core
it should be roughly flat vs. 1 shard; on multi-device deployments
each shard scans 1/S of the pages in parallel.

    PYTHONPATH=src python -m benchmarks.sharded_scan
    # mesh fan-out on a CPU host:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.sharded_scan
"""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.api import Database, IndexDescriptor, QueryGen, make_tuner_db


def _mk_db(src, num_shards: int, with_index: bool):
    db = Database(dict(src.tables), num_shards=num_shards)
    if with_index:
        bi = db.create_index(IndexDescriptor("narrow", (1,)), "vap")
        db.vap_build_step(bi, pages=src.tables["narrow"].n_pages // 2)
    return db

def _queries(src, n_queries: int, seed: int):
    gen = QueryGen(src, selectivity=0.01, seed=seed)
    return [gen.low_s(attr=1) if i % 2 == 0 else gen.mod_s()
            for i in range(n_queries)]


def _time_burst(fn, repeats: int) -> float:
    fn()                       # warm-up: compile every group shape
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def run(n_queries: int = 64, n_rows: int = 20_000, page_size: int = 256,
        shard_counts=(1, 2, 4), repeats: int = 3, quiet: bool = False):
    src = make_tuner_db(n_rows=n_rows, page_size=page_size)
    results = {}
    for label, with_index in (("table_scan", False), ("hybrid_scan", True)):
        qs = _queries(src, n_queries, seed=17)
        base_stats = None
        base_us = None
        for S in shard_counts:
            db = _mk_db(src, S, with_index)
            s_burst = _time_burst(lambda: db.execute_batch(qs), repeats)
            us_q = s_burst / n_queries * 1e6

            # Shard invariance: aggregates must match the 1-shard run.
            stats = [(r.agg_sum, r.count, r.cost_units)
                     for r in _mk_db(src, S, with_index).execute_batch(qs)]
            if base_stats is None:
                base_stats, base_us = stats, us_q
            assert stats == base_stats, \
                f"{label}: {S}-shard results diverge from 1-shard"

            tier = db.engine.last_tier
            rel = base_us / us_q
            results[(label, S)] = us_q
            emit(f"sharded_scan.{label}.shards{S}", us_q,
                 f"{n_queries}-query burst, tier {tier}, "
                 f"{rel:.2f}x vs 1 shard")
            if not quiet:
                print(f"# {label} S={S}: {us_q:.1f} us/q ({tier})")
    return results


if __name__ == "__main__":
    run()
