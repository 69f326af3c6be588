"""The scan kernels compile for a TPU v5e at the paper's table widths.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: block shapes that break the (8, 128) tiling rule,
more VMEM than a kernel may use, a program that does not fit the
device.  These tests lower each Pallas scan kernel with
``interpret=False`` and compile it for a *described* ``v5e:2x2``
topology -- no chip attached -- at TUNER narrow's 10M-row widths, with
the block size a chip launch would use (``tpu_block_pages``).  A
compiled program holding ``tpu_custom_call`` is a Mosaic kernel.
The VAP build program is compiled the same way, at the benchmark's
narrow and wide table shapes, to read what its HLO sorts and how much
temporary memory it takes.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
worker that runs this file keeps it until it exits.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.compile_cache import compile_cache_off
from repro.core import engine
from repro.core.index import AdHocIndex, _build_pages_vap
from repro.core.table import StackedShards, Table
from repro.kernels import batched_filter_agg as bfa
from repro.kernels import ops
from repro.parallel.mesh import SHARD_AXIS

# TUNER narrow at the paper's scale: 10M rows, headroom 1.5, 256-row
# pages -> 58594 pages; 4 round-robin shards hold 14649 pages each.
N_PAGES = 58594
PAGE_SIZE = 256
N_ATTRS = 21
BATCH = 16
SHARDS = 4
LOCAL_PAGES = -(-N_PAGES // SHARDS)
BLOCK_PAGES = bfa.tpu_block_pages(N_PAGES, PAGE_SIZE)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off.
    with compile_cache_off():
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), (SHARD_AXIS,))


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_mosaic(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_block_pages_for_the_paper_table():
    # 5 streamed int32 planes of 256 lanes, double-buffered in 4 MiB.
    assert BLOCK_PAGES == 256


@pytest.mark.parametrize(
    "batch, starts",
    [(BATCH, True), (1, False), (1, True)],
    ids=["batch", "one_full", "one_hybrid"],
)
def test_batched_filter_agg_compiles(one_chip, batch, starts):
    """A burst, and one statement as a batch of one: a table scan (no
    stitch points; the kernel gets zeros, as ``ops.scan_table_batched``
    passes it) and a hybrid scan's table suffix."""
    plane = _i32((N_PAGES, PAGE_SIZE), one_chip)
    per_query = _i32((batch,), one_chip)
    program, operands = bfa.batched_filter_agg, [per_query] * 6
    if not starts:

        @functools.partial(
            jax.jit, static_argnames=("block_pages", "interpret")
        )
        def program(*args, **kw):
            zeros = jnp.zeros((batch,), jnp.int32)
            return bfa.batched_filter_agg(*args, zeros, **kw)

        operands = operands[:5]
    compiled = _assert_mosaic(
        program.lower(
            *[plane] * 5,
            *operands,
            block_pages=BLOCK_PAGES,
            interpret=False,
        )
    )
    # The outputs are the (B,) sums and counts, nothing per block.
    assert compiled.memory_analysis().output_size_in_bytes < 4096


@pytest.mark.parametrize("masked", [False, True], ids=["starts", "masked"])
def test_sharded_batched_filter_agg_compiles(one_chip, masked):
    plane = _i32((SHARDS, LOCAL_PAGES, PAGE_SIZE), one_chip)
    per_query = _i32((BATCH,), one_chip)
    local_pages = _i32((SHARDS,), one_chip)
    if masked:
        kernel = bfa.sharded_batched_filter_agg_masked
        table = _i32((SHARDS, -(-LOCAL_PAGES // 32)), one_chip)
    else:
        kernel = bfa.sharded_batched_filter_agg
        table = _i32((SHARDS, BATCH), one_chip)
    _assert_mosaic(
        kernel.lower(
            *[plane] * 5,
            *[per_query] * 5,
            table,
            local_pages,
            block_pages=BLOCK_PAGES,
            interpret=False,
        )
    )


@pytest.mark.parametrize("masked", [False, True], ids=["starts", "masked"])
def test_mesh_kernel_suffix_compiles_on_four_chips(mesh, masked):
    """The per-local-shard kernel suffix inside the engine's
    ``shard_map`` body, one shard per chip of the 2x2 mesh.  This
    process's backend is the CPU, so the test steers ``ops`` off
    interpret mode the way a TPU backend would."""
    shard = NamedSharding(mesh, P(SHARD_AXIS))
    rep = NamedSharding(mesh, P())
    planes = (SHARDS, LOCAL_PAGES, PAGE_SIZE)
    stk = StackedShards(
        table=Table(
            _i32(planes + (N_ATTRS,), shard),
            _i32(planes, shard),
            _i32(planes, shard),
            _i32((SHARDS,), shard),
        ),
        shard_ids=_i32((SHARDS,), shard),
        local_pages=_i32((SHARDS,), shard),
    )
    bounds = _i32((BATCH, 1), rep)
    tss = _i32((BATCH,), rep)
    if masked:
        suffix = engine._mesh_kernel_suffix_masked
        extra = _i32((SHARDS, -(-LOCAL_PAGES // 32)), shard)
    else:
        suffix = engine._mesh_kernel_suffix
        extra = _i32((SHARDS, BATCH), shard)

    def body(stk, los, his, tss, extra):
        sums, cnts = suffix(stk, (3,), los, his, tss, 2, extra)
        return (
            jax.lax.psum(sums, SHARD_AXIS),
            jax.lax.psum(cnts, SHARD_AXIS),
        )

    program = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(), P(), P(), P(SHARD_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "interpret_mode", lambda: False)
        compiled = _assert_mosaic(program.lower(stk, bounds, bounds, tss, extra))
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize(
    "n_pages, n_attrs",
    [(N_PAGES, N_ATTRS), (35157, 201)],
    ids=["narrow_10M", "wide_6M"],
)
def test_vap_build_merges_without_capacity_sort(one_chip, n_pages, n_attrs):
    """A VAP build quantum at the benchmark's table shapes sorts only
    its own 64-page chunk and slices the key plane out of the table:
    no sort as long as the index, and no copy of the table."""
    capacity = n_pages * PAGE_SIZE
    vec = _i32((capacity,), one_chip)
    scalar = _i32((), one_chip)
    index = AdHocIndex(vec, vec, vec, scalar, scalar)
    planes = (n_pages, PAGE_SIZE)
    table = Table(
        _i32(planes + (n_attrs,), one_chip),
        _i32(planes, one_chip),
        _i32(planes, one_chip),
        scalar,
    )
    compiled = _build_pages_vap.lower(
        index, table, (1,), scalar, bucket=64
    ).compile()
    text = compiled.as_text()
    sorts = [line for line in text.splitlines() if re.search(r"\bsort\(", line)]
    assert sorts
    for line in sorts:
        head = line.split("sort(")[0]
        assert max(map(int, re.findall(r"\[(\d+)", head))) <= 64 * PAGE_SIZE
    table_bytes = 4 * capacity * n_attrs
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < min(1e9, table_bytes / 2)


@pytest.mark.parametrize(
    "n_pages, n_attrs",
    [(N_PAGES, N_ATTRS), (35157, 201)],
    ids=["narrow_10M", "wide_6M"],
)
def test_update_writes_its_planes_in_place(one_chip, n_pages, n_attrs):
    """An UPDATE at the benchmark's table shapes writes into the planes
    it is given: all three are aliased to the outputs, no second
    table-sized output is allocated, it sorts nothing, and it needs no
    temporaries in device memory."""
    from repro.core.table import _update_program

    planes = (n_pages, PAGE_SIZE)
    table = (
        _i32(planes + (n_attrs,), one_chip),
        _i32(planes, one_chip),
        _i32(planes, one_chip),
    )
    scalar = _i32((), one_chip)
    bounds = _i32((1,), one_chip)
    sets = _i32((3,), one_chip)
    compiled = _update_program.lower(
        table, scalar, (1,), bounds, bounds, sets, sets, scalar, max_new=512
    ).compile()
    mem = compiled.memory_analysis()
    # The chip's tiled layout pads the page axis to a multiple of 8.
    plane_bytes = 4 * n_pages * PAGE_SIZE * (n_attrs + 2)
    assert plane_bytes <= mem.alias_size_in_bytes < 1.001 * plane_bytes
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 4096
    # Measured 0 B at both shapes (the match mask and the selection fit
    # the core's own memory), so twice that is still 0.
    assert mem.temp_size_in_bytes <= 2 * 0
    sorts = [line for line in compiled.as_text().splitlines()
             if re.search(r"\bsort\(", line)]
    assert not sorts
