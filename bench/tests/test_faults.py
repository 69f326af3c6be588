"""The comparison finds a broken timed path: with each fault planted
in the program underneath a tiny run, ``correct`` comes out false.

The cells run on one chip, so the fault of an exchange between chips
left out does not arise.
"""

import pytest

from repro.core import executor

NARROW = "tuner_narrow.shift_read_heavy"
WIDE = "tuner_wide.analytic_scan"


def unchanged_update(monkeypatch):
    """An UPDATE that reports its rows but leaves the table as it was."""
    orig = executor.Database._exec_update

    def stale(self, q):
        before = self.tables[q.table]
        stats = orig(self, q)
        self.tables[q.table] = before
        return stats

    monkeypatch.setattr(executor.Database, "_exec_update", stale)


def half_batch(monkeypatch):
    """A burst of which only the first half is run; the rest is answered
    with the results of the half that ran."""
    orig = executor.Database.execute_batch

    def half(self, queries, *args, **kw):
        k = max(1, len(queries) // 2)
        out = orig(self, queries[:k], *args, **kw)
        return out + [out[i % k] for i in range(len(queries) - k)]

    monkeypatch.setattr(executor.Database, "execute_batch", half)


def altered_answer(monkeypatch):
    """One answer of every burst altered where it is produced."""
    orig = executor.Database.execute_batch

    def alter(self, queries, *args, **kw):
        out = orig(self, queries, *args, **kw)
        out[0].agg_sum += 1
        return out

    monkeypatch.setattr(executor.Database, "execute_batch", alter)


@pytest.mark.parametrize("cell,fault", [
    (NARROW, unchanged_update),
    (NARROW, half_batch),
    (NARROW, altered_answer),
    (WIDE, half_batch),
    (WIDE, altered_answer),
], ids=lambda x: x if isinstance(x, str) else x.__name__)
def test_fault_is_not_correct(run_tiny, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = run_tiny(cell, 2**33 + 11, seconds=3.0)
    assert not r["correct"]
    assert r["checks"]["statements_differing"]["value"] > 0
