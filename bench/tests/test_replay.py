"""The plain reference: its MVCC semantics, and its agreement with the
engine through the statement API."""

import numpy as np
import pytest

from bench import data
from bench.replay import NumpyReplay, columns_read
from bench.workload import StatementStream
from repro import api
from repro.core.table import Table


def small_table(rows=6000, seed=5):
    cfg = {"n_rows": rows, "page_size": 256, "headroom": 1.5,
           "n_attrs": 6, "zipf_a": 1.25, "domain": 1_000_000}
    arrays = data.make_table(cfg, seed)
    return cfg, arrays


def planes_of(arrays, attrs):
    return data.read_planes(arrays[0], attrs)


def test_update_is_seen_by_later_scans_only():
    planes = {1: np.array([5, 5, 7, 0]), 2: np.array([10, 20, 30, 0])}
    ref = NumpyReplay(planes, n_rows=3, update_cap=512)
    scan = api.Query(kind="scan", table="t", attrs=(1,), los=(5,), his=(5,),
                     agg_attr=2)
    upd = api.Query(kind="update", table="t", attrs=(1,), los=(7,),
                    his=(7,), set_attrs=(1,), set_vals=(5,))
    assert ref.run(0, scan) == (30, 2, 0)
    assert ref.run(1, upd) == (0, 0, 1)
    assert ref.run(2, scan) == (60, 3, 0)  # the new version of row 2


def test_sum_wraps_to_int32():
    big = 2**30
    planes = {1: np.ones(4, np.int64), 2: np.full(4, big)}
    ref = NumpyReplay(planes, n_rows=4, update_cap=512)
    q = api.Query(kind="scan", table="t", attrs=(1,), los=(1,), his=(1,),
                  agg_attr=2)
    assert ref.run(0, q) == (0, 4, 0)  # 4 * 2**30 wraps to 0


def test_sorted_path_equals_masks():
    cfg, arrays = small_table()
    qs = data.quantiles(arrays[0], cfg["n_rows"])
    traffic = {"table": "t", "scan": {"selectivity": 0.3,
                                      "projectivity": 0.1, "agg_attr": 2},
               "update": {"selectivity": 0.01, "n_set": 2},
               "mix": {"update_frac": 0.0, "update_block": 10,
                       "high_u_share": 0.5},
               "phase_len": 7, "phase_attrs": {"count": 2, "seed": 1}}
    stream = StatementStream(api.Query, traffic, 6, qs, 3)
    queries = [s.query for s in stream.take(40)]
    planes = planes_of(arrays, columns_read(queries))
    fast = NumpyReplay(planes, cfg["n_rows"], 512, read_only=True)
    slow = NumpyReplay(planes, cfg["n_rows"], 512, read_only=False)
    assert fast.run_all(queries) == slow.run_all(queries)


@pytest.mark.parametrize("burst", [1, 4])
def test_replay_agrees_with_engine(burst):
    cfg, arrays = small_table()
    qs = data.quantiles(arrays[0], cfg["n_rows"])
    traffic = {"table": "t", "scan": {"selectivity": 0.05,
                                      "projectivity": 0.2, "agg_attr": 2},
               "update": {"selectivity": 0.01, "n_set": 3},
               "mix": {"update_frac": 0.25, "update_block": 8,
                       "high_u_share": 0.5},
               "phase_len": 12, "phase_attrs": {"count": 2, "seed": 4}}
    stream = StatementStream(api.Query, traffic, 6, qs, 11)
    queries = [s.query for s in stream.take(48)]
    db = api.Database({"t": Table(*arrays)})
    db.create_index(api.IndexDescriptor("t", (queries[0].attrs[0],)), "vap")
    bi = next(iter(db.indexes.values()))
    db.vap_build_step(bi, 8)
    got = []
    i = 0
    while i < len(queries):
        group = [queries[i]]
        while (burst > 1 and len(group) < burst and i + len(group) <
               len(queries) and queries[i + len(group)].kind == "scan"
               and group[0].kind == "scan"):
            group.append(queries[i + len(group)])
        stats = db.execute_batch(group)
        got += [(s.agg_sum, s.count, s.rows_modified) for s in stats]
        i += len(group)
    planes = planes_of(arrays, columns_read(queries))
    want = NumpyReplay(planes, cfg["n_rows"], db.update_cap).run_all(queries)
    assert got == want
    assert any(q.kind == "update" for q in queries)
