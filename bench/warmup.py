"""Compile every program shape a cell's window will run, before it.

The window's programs are keyed by the predicate attributes, the index
key, the access path and the number of statements in a dispatch group.
The traffic fixes the attributes (``workload.phase_attrs``), so the
set of shapes is the same for every seed.  Each shape is run once here
through the statement API, on scratch ``Database`` objects over the
same tables (tables are immutable values, so the scratch writes touch
nothing the window reads):

* table-plan scan groups of every size the loop can form, for every
  phase attribute;
* an index on each key the tuner can build from the mix's predicates,
  as the traffic's ``index_keys`` lists them (``single``: the phase
  attribute; ``pair``: the pair an HIGH-U update names), with one
  build quantum; and where ``index_scans`` holds (the mix's scans are
  selective enough for the planner to take an index), index-plan scan
  groups of every size through it;
* an UPDATE of each predicate shape the mix holds;
* two tuning cycles of a scratch tuner that has seen those statements
  (its forecaster programs and the build quanta it sizes itself).

What the tuner then builds in the window reuses these programs.
"""

from __future__ import annotations

from typing import List

from bench.workload import StatementStream, phase_attrs


def group_sizes(traffic: dict) -> List[int]:
    """Sizes a dispatch group can take in the cell's loop."""
    loop = traffic["loop"]
    if loop["kind"] == "open" or float(traffic["mix"]["update_frac"]) > 0:
        top = int(loop.get("burst_max", loop.get("clients", 1)))
        return list(range(1, top + 1))
    # Closed loop of scans only: bursts of ``clients`` statements in
    # order, split where a phase ends.
    k, phase = int(loop["clients"]), int(traffic["phase_len"])
    sizes = set()
    period = k * phase  # the split pattern repeats
    for start in range(0, period, k):
        a, b = start, start + k
        cut = (a // phase + 1) * phase
        if cut < b:
            sizes.update((cut - a, b - cut))
        else:
            sizes.add(k)
    return sorted(sizes)


def _pair(attrs, i, n_attrs):
    a = attrs[i % len(attrs)]
    b = attrs[(i + 1) % len(attrs)]
    return a, (b if b != a else (a % n_attrs) + 1)


def warm(api, tables, traffic: dict, n_attrs: int, quantiles, seed: int,
         build_pages: int, make_tuner) -> int:
    """Run every shape once; returns the number of statements run.
    ``make_tuner(db)`` makes a tuner as the cell configures it."""
    table = traffic["table"]
    gen = StatementStream(api.Query, traffic, n_attrs, quantiles,
                          seed ^ 0x3A3A3A3A)
    attrs = phase_attrs(traffic, n_attrs)
    sizes = group_sizes(traffic)
    has_updates = float(traffic["mix"]["update_frac"]) > 0
    n = 0

    def scans(db, q, size, tuner=None):
        nonlocal n
        stats = db.execute_batch([q] * size)
        if tuner is not None:
            for st in stats:
                tuner.on_query(q, st)
        n += size

    for i, a in enumerate(attrs):
        pair = _pair(attrs, i, n_attrs)
        db = api.Database(dict(tables))
        tuner = make_tuner(db)
        q = gen.low_s(a)
        for size in sizes:  # no index: table plans
            scans(db, q, size, tuner)
        if has_updates:
            for u in (gen.low_u(a), gen.high_u(pair)):
                tuner.on_query(u, db.execute(u))
                n += 1
        for _ in range(2):
            tuner.tuning_cycle(idle=False)
        del db, tuner
        # A point predicate: the planner takes an index for it.
        mid = int(quantiles[len(quantiles) // 2])
        point = api.Query(kind="scan", table=table, attrs=(a,), los=(mid,),
                          his=(mid,), agg_attr=q.agg_attr,
                          proj_attrs=q.proj_attrs, template=q.template)
        kinds = traffic.get("index_keys", [])
        keys = [(a,)] * ("single" in kinds) + [pair] * ("pair" in kinds)
        for key in keys:
            db = api.Database(dict(tables))
            bi = db.create_index(api.IndexDescriptor(table, key), "vap")
            db.vap_build_step(bi, build_pages)
            if traffic.get("index_scans"):
                for size in sizes:
                    scans(db, point, size)
            del db, bi
    return n
