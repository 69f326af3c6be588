"""TUNER tables made on the device from the run's seed.

The paper's TUNER schema (Section V): a table of ``n_rows`` tuples, each
an ``a_0`` timestamp (the row's position, 1-based) and ``n_attrs``
integer attributes drawn from a Zipf distribution with exponent
``zipf_a`` and folded into ``[1, domain]`` by the multiplicative hash
that ``repro.bench_db.schema.zipf_attrs`` uses.  Rows fill pages of
``page_size`` in rid order; ``headroom`` reserves further pages for
MVCC appends.  Loaded rows begin at timestamp 0 and never end.

The whole table is one jitted call from the seed: every value is a pure
function of (seed, attribute, rid), computed element-wise, so XLA
writes the output in one fused pass with no full-size temporary, and
the seed is a traced operand, so every seed runs the same program.

The Zipf draw is the rejection method numpy's ``Generator.zipf`` uses
(Devroye), with a fixed number of proposal rounds instead of a loop:
each round accepts with probability about 0.73 at ``a = 1.25``, and a
value still unaccepted after ``ZIPF_ROUNDS`` rounds (about 3e-5 of
them) keeps its last proposal.  Uniforms come from a 32-bit integer
hash of (seed, attribute, round, rid), not from numpy's bit stream, so
the values differ from ``make_tuner_db``'s while their distribution is
the same.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ZIPF_ROUNDS = 8
FOLD_MULT = 2654435761  # repro.bench_db.schema.zipf_attrs's fold
NEVER_TS = 2**31 - 1  # begin_ts of an empty slot; end_ts of a live row


def table_geometry(cfg: dict) -> dict:
    """Pages, capacity and logical bytes of the table ``cfg`` states."""
    n_rows, psz = int(cfg["n_rows"]), int(cfg["page_size"])
    n_pages = int(math.ceil(n_rows / psz * float(cfg["headroom"])))
    n_cols = int(cfg["n_attrs"]) + 1  # a_0 plus the attributes
    cap = n_pages * psz
    return {
        "n_rows": n_rows,
        "page_size": psz,
        "n_pages": n_pages,
        "n_cols": n_cols,
        "capacity": cap,
        "logical_bytes": cap * n_cols * 4 + 2 * cap * 4,
    }


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words of a non-negative seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _mix(x):
    """32-bit finaliser (lowbias32): a bijection that spreads every
    input bit over the output."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _unit(h):
    """uint32 hash -> float32 uniform in (0, 1), 24 bits."""
    return ((h >> 8).astype(jnp.float32) + 0.5) * jnp.float32(2.0**-24)


def _fold(x, domain: int):
    """``(x * FOLD_MULT) % domain + 1`` in 32-bit arithmetic for a
    float32 Zipf value ``x >= 1``.  Values up to 2**24 are exact
    integers in float32 and fold exactly; larger ones (under 2% of
    draws) fold from their float32 bit pattern, which spreads them over
    the domain like the exact fold of the tail does."""
    exact = x < jnp.float32(2.0**24)
    xi = jnp.where(
        exact,
        x.astype(jnp.int32),
        jax.lax.bitcast_convert_type(x, jnp.int32) & 0x7FFFFFFF,
    )
    r = xi % domain  # x mod domain
    c = FOLD_MULT % domain
    # (r * c) % domain without 64-bit products: split r = hi * 1000 + lo.
    hi, lo = r // 1000, r % 1000
    part = ((hi * c) % domain) * 1000 % domain
    return (part + lo * c) % domain + 1


def _zipf(salt, rid, a: float, domain: int):
    am1 = a - 1.0
    b = 2.0**am1
    umin = float(2.0**63) ** (-am1)
    hr = _mix(rid.astype(jnp.uint32) + jnp.uint32(0x27D4EB2F))
    out = None
    done = None
    for k in range(ZIPF_ROUNDS):
        su = _mix(salt ^ jnp.uint32((2 * k + 1) * 0x85EBCA6B & 0xFFFFFFFF))
        sv = _mix(salt ^ jnp.uint32((2 * k + 2) * 0x85EBCA6B & 0xFFFFFFFF))
        u01 = _unit(_mix(su ^ hr))
        v = _unit(_mix(sv ^ hr))
        u = u01 * jnp.float32(umin) + (1.0 - u01)
        x = jnp.floor(u ** jnp.float32(-1.0 / am1))
        x = jnp.maximum(x, 1.0)
        tm1 = jnp.expm1(jnp.float32(am1) * jnp.log1p(1.0 / x))
        ok = v * x * tm1 / jnp.float32(b - 1.0) <= (1.0 + tm1) / jnp.float32(b)
        if out is None:
            out, done = x, ok
        else:
            take = ok & ~done
            out = jnp.where(take, x, out)
            done = done | ok
        if k == ZIPF_ROUNDS - 1:
            out = jnp.where(done, out, x)
    return _fold(out, domain)


@functools.partial(
    jax.jit,
    static_argnames=("n_pages", "page_size", "n_cols", "n_rows", "a",
                     "domain"),
)
def _make_table(words, n_pages, page_size, n_cols, n_rows, a, domain):
    shape = (n_pages, page_size, n_cols)
    page = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    rid = page * page_size + slot
    base = _mix(words[0] ^ _mix(words[1] + jnp.uint32(0x9E3779B9)))
    salt = _mix(base ^ (col.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)))
    vals = _zipf(salt, rid, a, domain)
    vals = jnp.where(col == 0, rid + 1, vals)
    data = jnp.where(rid < n_rows, vals, 0).astype(jnp.int32)
    rid2 = jax.lax.broadcasted_iota(jnp.int32, shape[:2], 0) * page_size
    rid2 = rid2 + jax.lax.broadcasted_iota(jnp.int32, shape[:2], 1)
    begin = jnp.where(rid2 < n_rows, 0, NEVER_TS).astype(jnp.int32)
    end = jnp.full((n_pages, page_size), NEVER_TS, jnp.int32)
    return data, begin, end


def make_table(cfg: dict, seed: int):
    """(data, begin_ts, end_ts, n_rows) device arrays of the TUNER table
    that ``cfg`` states, for ``seed``."""
    g = table_geometry(cfg)
    if not 1 <= int(cfg["domain"]) <= 1_000_000:
        raise ValueError("the 32-bit fold needs a domain of at most 1M")
    data, begin, end = _make_table(
        jnp.asarray(seed_words(seed)),
        n_pages=g["n_pages"],
        page_size=g["page_size"],
        n_cols=g["n_cols"],
        n_rows=g["n_rows"],
        a=float(cfg["zipf_a"]),
        domain=int(cfg["domain"]),
    )
    return data, begin, end, jnp.asarray(g["n_rows"], jnp.int32)


@functools.partial(jax.jit, static_argnames=("attr", "n_rows"))
def _sorted_column(data, attr, n_rows):
    return jnp.sort(data[:, :, attr].reshape(-1)[:n_rows])


def quantiles(data, n_rows: int, attr: int = 1) -> np.ndarray:
    """Sorted values of one attribute over the loaded rows (all
    attributes share one distribution), sorted on the device."""
    return np.asarray(_sorted_column(data, attr=attr, n_rows=n_rows))


@functools.partial(jax.jit, static_argnames=("attr",))
def _plane(data, attr):
    return data[:, :, attr].reshape(-1)


def read_planes(data, attrs) -> dict:
    """Host copies of the columns ``attrs`` over the whole capacity."""
    return {int(a): np.array(_plane(data, attr=int(a))) for a in attrs}
