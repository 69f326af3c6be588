"""Traffic from the seed: the same seed gives the same statements, and
every seed gives the same amount of work in another order."""

import collections

import numpy as np
import pytest

from bench import spec
from bench.workload import StatementStream, arrivals, phase_attrs
from repro import api

QS = np.sort(np.random.default_rng(0).integers(1, 1_000_000, 50_000))


def stream(traffic_name, seed):
    tr = spec.traffic(traffic_name)
    return StatementStream(api.Query, tr, 20, QS, seed), tr


def test_same_seed_same_statements():
    a, _ = stream("shift_read_heavy", 2**33 + 1)
    b, _ = stream("shift_read_heavy", 2**33 + 1)
    assert [s.query for s in a.take(300)] == [s.query for s in b.take(300)]


def test_seeds_change_order_not_amount():
    kinds = []
    attrs = []
    for seed in (1, 2**31 + 7, 2**40 + 3):
        s, tr = stream("shift_read_heavy", seed)
        got = s.take(200)
        kinds.append(collections.Counter(x.query.template for x in got))
        attrs.append(sorted({x.query.attrs for x in got}))
    assert kinds[0] == kinds[1] == kinds[2]
    assert kinds[0]["LOW-U"] == kinds[0]["HIGH-U"] == 10
    assert attrs[0] == attrs[1] == attrs[2]


def test_phase_attrs_cycle_every_phase_len():
    s, tr = stream("shift_read_heavy", 5)
    pa = phase_attrs(tr, 20)
    for x in s.take(400):
        assert x.query.attrs[0] == pa[x.phase % len(pa)]


def test_poisson_arrivals_at_the_rate():
    loop = {"arrivals": "poisson", "rate_per_s": 4.0}
    a = arrivals(loop, 4000, 1)
    b = arrivals(loop, 4000, 2**35)
    assert not np.allclose(a, b)
    assert np.all(np.diff(a) > 0)
    assert abs(np.diff(a, prepend=0.0).mean() - 0.25) < 0.02


def test_arrivals_same_seed_same_times():
    loop = {"arrivals": "poisson", "rate_per_s": 3.0}
    assert np.array_equal(arrivals(loop, 50, 9), arrivals(loop, 50, 9))


def test_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError):
        arrivals({"arrivals": "uniform", "rate_per_s": 3.0}, 10, 1)
