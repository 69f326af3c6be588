"""Statement streams and arrival times, from a traffic file and a seed.

A copy of the TUNER templates (``repro.bench_db.queries.QueryGen``:
LOW-S, LOW-U, HIGH-U; paper Section V-A), of the phased scan/update
mix (``repro.bench_db.workloads.hybrid_workload``; Section V-B) and of
the arrival arithmetic of ``repro.serving.admission``, kept here so
that the yardstick does not move with the program.  The program gets
only the generated ``Query`` objects.

Two departures from those generators keep the mix of every seed the
same, so that seeds change the order of work and not its kinds:

* the set of phase attributes comes from the traffic file's own
  ``phase_attrs.seed`` and not from the run's seed, so every run uses
  the same predicate columns (and the same compiled programs), in
  phases of ``phase_len`` statements that cycle through that set;
* the mix is exact per block: every ``update_block`` statements hold
  exactly ``round(update_frac * update_block)`` updates, at positions
  drawn from the seed, alternating LOW-U and HIGH-U in a seeded order
  (``hybrid_workload`` draws each statement's kind independently).

The run's seed draws everything else: the data, the predicate
positions (uniform, as ``QueryGen`` draws them), SET lists and values,
the projection list, update positions and the Poisson arrivals.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

DOMAIN = 1_000_000


@dataclass
class Stmt:
    """One generated statement: its phase and query."""

    phase: int
    query: object  # repro.api.Query


class QuantileBounds:
    """Predicate bounds from a sorted sample of attribute values
    (``TunerDB.quantile_bounds``)."""

    def __init__(self, sorted_values: np.ndarray):
        self.qs = np.asarray(sorted_values)

    def bounds(self, sel: float, pos: float):
        qs = self.qs
        n = len(qs)
        i0 = int(pos * (n - 1))
        i1 = min(int((pos + sel) * (n - 1)), n - 1)
        lo, hi = int(qs[i0]), int(qs[i1])
        if lo > hi:
            lo, hi = hi, lo
        return lo, hi


def phase_attrs(traffic: dict, n_attrs: int) -> List[int]:
    """The traffic's fixed, ordered set of predicate attributes
    (``n_attrs`` counts the attributes a_1..a_p, not a_0)."""
    spec = traffic["phase_attrs"]
    rng = np.random.default_rng(int(spec["seed"]))
    picks = rng.choice(np.arange(1, n_attrs + 1), int(spec["count"]),
                       replace=False)
    return [int(a) for a in picks]


class StatementStream:
    """Endless statement stream of one traffic mix over one table."""

    def __init__(self, Query, traffic: dict, n_attrs: int,
                 quantiles: np.ndarray, seed: int):
        self.Query = Query
        self.t = traffic
        self.table = traffic["table"]
        self.n_attrs = n_attrs  # a_1..a_p
        self.qb = QuantileBounds(quantiles)
        self.attrs = phase_attrs(traffic, n_attrs)
        self.rng = np.random.default_rng([int(seed), 0x5EED])
        scan = traffic["scan"]
        k = max(1, int(round(float(scan["projectivity"]) * n_attrs)))
        self.proj = tuple(sorted(int(a) for a in self.rng.choice(
            np.arange(1, n_attrs + 1), size=k, replace=False)))
        self._block: List[str] = []
        self.next_index = 0

    # -- templates (QueryGen) ----------------------------------------------
    def _bounds(self, sel: float):
        """Bounds at a uniform quantile position in [0, 1 - sel)."""
        u = float(self.rng.uniform())
        return self.qb.bounds(sel, u * max(1.0 - sel, 1e-6))

    def low_s(self, attr: int):
        scan = self.t["scan"]
        lo, hi = self._bounds(float(scan["selectivity"]))
        return self.Query(kind="scan", table=self.table, attrs=(attr,),
                          los=(lo,), his=(hi,),
                          agg_attr=int(scan["agg_attr"]),
                          proj_attrs=self.proj, template="LOW-S")

    def _set_list(self):
        n_set = int(self.t["update"]["n_set"])
        set_attrs = tuple(int(a) for a in self.rng.choice(
            np.arange(1, self.n_attrs + 1), size=n_set, replace=False))
        set_vals = tuple(int(v) for v in
                         self.rng.integers(1, DOMAIN, size=n_set))
        return set_attrs, set_vals

    def low_u(self, attr: int):
        lo, hi = self._bounds(float(self.t["update"]["selectivity"]))
        set_attrs, set_vals = self._set_list()
        return self.Query(kind="update", table=self.table, attrs=(attr,),
                          los=(lo,), his=(hi,), set_attrs=set_attrs,
                          set_vals=set_vals, template="LOW-U")

    def high_u(self, attrs):
        s_each = float(np.sqrt(float(self.t["update"]["selectivity"])))
        lo0, hi0 = self._bounds(s_each)
        lo1, hi1 = self._bounds(s_each)
        set_attrs, set_vals = self._set_list()
        return self.Query(kind="update", table=self.table,
                          attrs=tuple(attrs), los=(lo0, lo1),
                          his=(hi0, hi1), set_attrs=set_attrs,
                          set_vals=set_vals, template="HIGH-U")

    # -- the mix -----------------------------------------------------------
    def _refill_block(self) -> None:
        mix = self.t["mix"]
        n = int(mix["update_block"])
        n_upd = int(round(float(mix["update_frac"]) * n))
        kinds = ["scan"] * (n - n_upd)
        n_high = int(round(float(mix["high_u_share"]) * n_upd))
        kinds += ["high_u"] * n_high + ["low_u"] * (n_upd - n_high)
        self._block = [kinds[i] for i in self.rng.permutation(n)]

    def phase_of(self, index: int) -> int:
        return index // int(self.t["phase_len"])

    def next(self) -> Stmt:
        if not self._block:
            self._refill_block()
        kind = self._block.pop(0)
        i = self.next_index
        self.next_index += 1
        phase = self.phase_of(i)
        a = self.attrs[phase % len(self.attrs)]
        if kind == "scan":
            q = self.low_s(a)
        elif kind == "low_u":
            q = self.low_u(a)
        else:
            b = self.attrs[(phase + 1) % len(self.attrs)]
            q = self.high_u((a, b if b != a else (a % self.n_attrs) + 1))
        return Stmt(phase, q)

    def take(self, n: int) -> List[Stmt]:
        return [self.next() for _ in range(n)]


# ---------------------------------------------------------------------------
# Arrivals (repro.serving.admission), in seconds from the window's start
# ---------------------------------------------------------------------------


def poisson_arrivals(n: int, mean_s: float, seed: int) -> np.ndarray:
    """Poisson process: exponential gaps of mean ``mean_s``."""
    rng = np.random.default_rng([int(seed), 0xA551])
    return np.cumsum(rng.exponential(mean_s, size=n))


def arrivals(loop: dict, n: int, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of n statements offered at
    the open loop's ``rate_per_s``; ``arrivals`` must be ``poisson``."""
    if loop["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival kind {loop['arrivals']!r}")
    return poisson_arrivals(n, 1.0 / float(loop["rate_per_s"]), seed)
