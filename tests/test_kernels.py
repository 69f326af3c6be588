"""Pallas kernel validation: shape sweep in interpret mode against the
pure-jnp oracles in repro.kernels.ref.  A single query runs as a
batch of one."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.table import load_table, update_rows
from repro.kernels import ops
from repro.kernels.batched_filter_agg import batched_filter_agg
from repro.kernels.ref import filter_agg_ref, masked_filter_agg_ref


def _mk(n_rows, page_size, n_attrs=5, seed=0, vmax=1000):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, vmax, size=(n_rows, n_attrs)).astype(np.int32)
    return load_table(vals, page_size=page_size), vals


SHAPES = [(256, 128), (1000, 128), (4096, 256), (511, 128), (130, 128)]


def _scan_one(t, attrs, los, his, ts, agg_attr, start_page=None):
    """One query through the batched kernel: (sum, count) ints."""
    starts = None if start_page is None else np.array([start_page])
    s, c = ops.scan_table_batched(
        t, attrs, np.array([los], np.int32), np.array([his], np.int32),
        np.array([ts], np.int32), agg_attr, start_pages=starts)
    assert s.shape == c.shape == (1,)
    return int(s[0]), int(c[0])


@pytest.mark.parametrize("n_rows,page_size", SHAPES)
def test_filter_agg_matches_ref(n_rows, page_size):
    t, _ = _mk(n_rows, page_size, seed=n_rows)
    for attrs, lo, hi in [((1,), 100, 700), ((1, 3), 0, 999),
                          ((2,), 500, 500), ((1, 2), 250, 750)]:
        los = tuple([lo] * len(attrs))
        his = tuple([hi] * len(attrs))
        got = _scan_one(t, attrs, los, his, ts=0, agg_attr=4)
        p0 = t.data[:, :, attrs[0]]
        p1 = t.data[:, :, attrs[1]] if len(attrs) == 2 else p0
        l1 = los[1] if len(attrs) == 2 else ops.I32_MIN
        h1 = his[1] if len(attrs) == 2 else ops.I32_MAX
        rs, rc = filter_agg_ref(p0, p1, t.data[:, :, 4], t.begin_ts,
                                t.end_ts, lo, hi, l1, h1, 0)
        assert got == (int(rs), int(rc))


@settings(max_examples=25, deadline=None)
@given(start_page=st.integers(0, 40), seed=st.integers(0, 99),
       lo=st.integers(0, 900))
def test_hybrid_kernel_page_skip(start_page, seed, lo):
    t, _ = _mk(3000, 128, seed=seed)
    got = _scan_one(t, (1,), (lo,), (lo + 200,), ts=0, agg_attr=2,
                    start_page=start_page)
    rs, rc = masked_filter_agg_ref(
        t.data[:, :, 1], t.data[:, :, 1], t.data[:, :, 2], t.begin_ts,
        t.end_ts, lo, lo + 200, ops.I32_MIN, ops.I32_MAX, 0, start_page)
    assert got == (int(rs), int(rc))


def test_kernel_respects_mvcc_visibility():
    t, _ = _mk(512, 128, seed=7)
    t2, n = update_rows(t, (1,), jnp.array([0]), jnp.array([400]),
                        jnp.array([2]), jnp.array([9999]), ts=10,
                        max_new=128)
    for ts in (5, 15):
        got = _scan_one(t2, (1,), (0,), (999,), ts=ts, agg_attr=2)
        rs, rc = filter_agg_ref(t2.data[:, :, 1], t2.data[:, :, 1],
                                t2.data[:, :, 2], t2.begin_ts, t2.end_ts,
                                0, 999, ops.I32_MIN, ops.I32_MAX, ts)
        assert got == (int(rs), int(rc))


def test_kernel_block_shapes():
    """Different block_pages tilings must agree."""
    t, _ = _mk(2048, 128, seed=11)

    def one(x):
        return jnp.array([x], jnp.int32)

    outs = []
    for bp in (8, 16, 32, 64):
        s, c = batched_filter_agg(t.data[:, :, 1], t.data[:, :, 1],
                                  t.data[:, :, 2], t.begin_ts, t.end_ts,
                                  one(100), one(800), one(ops.I32_MIN),
                                  one(ops.I32_MAX), one(0), one(0),
                                  block_pages=bp, interpret=True)
        outs.append((int(s[0]), int(c[0])))
    assert len(set(outs)) == 1
