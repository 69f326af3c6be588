"""Microbenchmark: fused single-dispatch sharded scans vs the
per-shard loop fan-out.

The per-shard loop traces one vmapped scan body PER SHARD into every
burst program, so trace size and compile time grow ~S x with the
shard count (and with them the cost of every fresh burst shape);
the stacked forms vmap the identical body over a cached padded shard
pytree, so the program is the same size for any S (core/engine.py).
Both strategies are bit-identical (asserted here and in
tests/test_fused_shard_scan.py).

The headline measures *read-burst throughput on shape-shifting
bursts*: real burst sizes vary statement to statement, and every
fresh (batch, aggregate) shape pays a full trace+compile before its
dispatch -- on CPU that is hundreds of milliseconds against a
sub-millisecond steady dispatch, so burst throughput under shifting
shapes is exactly the ~S x trace tax the fused layout removes.  At
S=4 the fused hybrid burst sustains >= 2-3x the loop fan-out's
throughput; steady-state (pre-compiled shape) dispatch timings are
emitted as info records (they are a wash on one CPU core -- XLA runs
the loop's per-shard ops in parallel -- and become the multi-device
win via the pmap/TPU paths).

    PYTHONPATH=src python -m benchmarks.fused_shard_scan
"""
from __future__ import annotations

import time

import numpy as np

import jax.numpy as jnp

from benchmarks.common import emit
from repro.api import make_tuner_db
from repro.compile_cache import compile_cache_off
from repro.core import engine as eng
from repro.core.index import make_sharded_index, sharded_build_pages_vap
from repro.core.table import shard_table

HEADLINE_S = 4


def _bounds(n_queries, seed):
    rng = np.random.default_rng(seed)
    los = rng.integers(1, 5 * 10**5, size=(n_queries, 1)).astype(np.int32)
    his = los + 10_000
    tss = np.full((n_queries,), 5, np.int32)
    return jnp.asarray(los), jnp.asarray(his), jnp.asarray(tss)


def _steady_us(fn, inner=5, rounds=5):
    """Min-of-rounds steady-state time per call (compiled shape)."""
    fn()
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(inner):
            fn()
        best = min(best, (time.perf_counter() - t0) / inner)
    return best * 1e6


def _assert_bit_identical(st, ix, los, his, tss, S):
    pairs = (
        (eng.sharded_batched_full_table_scan_loop(st, (1,), los, his, tss, 2),
         eng.sharded_batched_full_table_scan(st, (1,), los, his, tss, 2)),
        (eng.sharded_batched_hybrid_scan_loop(
            st, ix, (1,), (1,), los, his, tss, 2),
         eng.sharded_batched_hybrid_scan(
            st, ix, (1,), (1,), los, his, tss, 2)),
    )
    for a, b in pairs:
        for f, x, y in zip(a._fields, a, b):
            assert (np.asarray(x) == np.asarray(y)).all(), \
                f"fused S={S} diverges from loop on {f}"


def run(n_queries: int = 16, n_rows: int = 4_096, page_size: int = 128,
        shard_counts=(1, 4, 8), bursts: int = 3, quiet: bool = False):
    src = make_tuner_db(n_rows=n_rows, page_size=page_size)
    t = src.tables["narrow"]
    headline = None

    for S in shard_counts:
        st = shard_table(t, S)
        ix = make_sharded_index(st)
        ix = sharded_build_pages_vap(ix, st, (1,), t.n_pages // 2)

        los, his, tss = _bounds(n_queries, seed=17)
        _assert_bit_identical(st, ix, los, his, tss, S)

        # Shape-shifting hybrid bursts: every burst is a fresh
        # (batch size, aggregate attr) combination, so each strategy
        # pays its own trace+compile per burst -- the dominant cost of
        # serving bursts whose shapes shift.
        shapes = [(n_queries - 1 - k, 3 + k) for k in range(bursts)]

        def run_bursts(fused: bool) -> float:
            total_q = 0
            t0 = time.perf_counter()
            for k, (B, agg) in enumerate(shapes):
                lo_k, hi_k, ts_k = _bounds(B, seed=100 * S + k)
                if fused:
                    r = eng.sharded_batched_hybrid_scan(
                        st, ix, (1,), (1,), lo_k, hi_k, ts_k, agg)
                else:
                    r = eng.sharded_batched_hybrid_scan_loop(
                        st, ix, (1,), (1,), lo_k, hi_k, ts_k, agg)
                r.agg_sum.block_until_ready()
                total_q += B
            return (time.perf_counter() - t0) / total_q * 1e6

        # These records time fresh compiles: a warm persistent cache
        # would turn them into cache reads.
        with compile_cache_off():
            us_loop = run_bursts(fused=False)
            us_fused = run_bursts(fused=True)
        speedup = us_loop / us_fused
        is_headline = S == HEADLINE_S
        if is_headline:
            headline = speedup
        # Absolute burst latency is compile-dominated (machine
        # sensitive) -> info; the within-run RATIO is the gated
        # headline record below.
        emit(f"fused_shard_scan.shifting_burst.shards{S}", us_fused,
             f"{bursts} fresh-shape hybrid bursts, fused single "
             f"dispatch, {speedup:.2f}x vs per-shard loop",
             speedup=speedup if is_headline else None, direction="info")
        emit(f"fused_shard_scan.shifting_burst.shards{S}.loop", us_loop,
             "per-shard loop fan-out baseline", direction="info")
        if not quiet:
            print(f"# shifting bursts S={S}: fused {us_fused:.0f}us/q vs "
                  f"loop {us_loop:.0f}us/q ({speedup:.2f}x)")

        # Steady state (compiled shape): a wash on one CPU core, the
        # multi-device win rides the pmap/TPU paths.  Info records.
        steady_loop = _steady_us(
            lambda: eng.sharded_batched_hybrid_scan_loop(
                st, ix, (1,), (1,), los, his, tss, 2
            ).agg_sum.block_until_ready()) / n_queries
        steady_fused = _steady_us(
            lambda: eng.sharded_batched_hybrid_scan(
                st, ix, (1,), (1,), los, his, tss, 2
            ).agg_sum.block_until_ready()) / n_queries
        emit(f"fused_shard_scan.steady.shards{S}", steady_fused,
             f"compiled-shape hybrid burst, "
             f"{steady_loop / steady_fused:.2f}x vs loop "
             f"({steady_loop:.1f}us/q)", direction="info")

    if headline is not None:
        emit("fused_shard_scan.headline_speedup_s4", headline,
             f"shape-shifting read-burst throughput, fused vs "
             f"per-shard loop at S={HEADLINE_S}",
             speedup=headline, direction="higher")
    return headline


if __name__ == "__main__":
    run()
