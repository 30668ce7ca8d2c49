"""Compile the main path for a described TPU v5e at deployment size.

Nothing here runs: each test lowers a jitted entry point of the serving
path against ``ShapeDtypeStruct`` arguments placed on the devices of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler that
ships with libtpu.  That catches what the Pallas interpreter cannot — a
kernel Mosaic refuses, VMEM or SMEM overflow, a program larger than the
chip's 16 GB — at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU here,
so the tests steer the lookup dispatcher onto the compiled kernel
themselves (``kernel_dispatch``).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import Schema, hashindex, snapshot as snap_mod
from repro.core import table as table_mod
from repro.core.table import IndexedTable
from repro.kernels import ops

ROWS = 1 << 24                 # rows of (k int64, v float32), as chip_smoke
RB = 4096                      # rows_per_batch (the facade default)
SLOTS = hashindex.DEFAULT_SLOTS
HBM = 16 * 10**9               # one v5e chip
SCH = Schema.of("k", k="int64", v="float32")
# Temporaries a lookup may hold whatever the table's size: tile windows,
# the query batch, the answer.  The bucket planes and ``prev`` reach the
# kernel as bitcasts, never as copies.
FIXED_TEMP = 16 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_dispatch(monkeypatch):
    """Resolve ``interpret=None`` as on a TPU backend: compiled kernel."""
    from repro.kernels import runtime
    monkeypatch.setattr(runtime, "resolve_interpret",
                        lambda interpret: False if interpret is None
                        else bool(interpret))


def _place(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _table_shapes(rows: int, num_shards: int | None = None) -> IndexedTable:
    """The abstract IndexedTable ``from_columns`` builds for ``rows`` rows
    (per shard, stacked on a leading axis when ``num_shards``), by
    ``eval_shape`` over the segment and snapshot constructors.  As there,
    only a distributed table stores flat row data in its snapshot."""
    cap = table_mod.capacity_class(rows, RB)
    nb = hashindex.suggest_num_buckets(cap, SLOTS)
    lead = () if num_shards is None else (num_shards,)

    def build(cols, valid, heads):
        seg, _ = table_mod.make_segment_arrays(
            cols, valid, heads, SCH, row_base=0, rows_per_batch=RB,
            layout="row", num_buckets=nb, slots=SLOTS)
        return seg, snap_mod.snapshot_from_segments(
            (seg,), "row", schema=SCH, with_data=num_shards is not None)

    sds = jax.ShapeDtypeStruct
    seg, snap = jax.eval_shape(
        build if num_shards is None else jax.vmap(build),
        {"k": sds(lead + (cap,), jnp.int64),
         "v": sds(lead + (cap,), jnp.float32)},
        sds(lead + (cap,), jnp.bool_), sds(lead + (cap,), jnp.int32))
    return IndexedTable(segments=(seg,), snapshot=snap,
                        version=sds(lead, jnp.int32), schema=SCH,
                        rows_per_batch=RB, layout="row", slots=SLOTS)


def _row_relayout_bytes(rows: int) -> int:
    """Bytes of the one O(capacity) temporary a read site holds today:
    the row gather relayouts the flat ``[cap, 3]`` int32 row words into
    4-word tiles on every call (an open question in PERF.md).  Pinned so
    that any further per-call copy of the table shows."""
    return table_mod.capacity_class(rows, RB) * 4 * 4


def _check(compiled, *, kernel: bool, max_temp: int | None = None):
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM, total
    if max_temp is not None:
        assert mem.temp_size_in_bytes <= max_temp, mem.temp_size_in_bytes
    return mem.temp_size_in_bytes


@pytest.mark.parametrize("max_matches", [1, 64])
def test_fused_lookup_compiles_at_deployment_size(one_chip, max_matches):
    """The default TPU read path: one table of 2^24 rows on one chip.
    No temporary grows with the table (same temp bytes at a quarter of
    the rows)."""
    temps = []
    for rows in (ROWS // 4, ROWS):
        snap = _place(snap_mod.strip_data(_table_shapes(rows).snapshot),
                      one_chip)
        q = jax.ShapeDtypeStruct((4096,), jnp.int64, sharding=one_chip)
        compiled = ops._fused_kernel_jit.lower(
            q, snap, max_matches=max_matches, interpret=False).compile()
        temps.append(_check(compiled, kernel=True, max_temp=FIXED_TEMP))
    assert temps[0] == temps[1], temps


def test_fused_lookup_vmapped_over_shards_compiles(one_chip):
    """The one-chip vmap runtime: 2^24 rows over 4 stacked shards.  The
    stacked ``prev`` and planes are not copied per call (same temp bytes
    at a quarter of the rows)."""
    look = functools.partial(ops._fused_kernel_jit, max_matches=64,
                             interpret=False)
    temps = []
    for rows in (ROWS // 4, ROWS):
        snap = _place(snap_mod.strip_data(
            _table_shapes(rows // 4, num_shards=4).snapshot), one_chip)
        q = jax.ShapeDtypeStruct((4, 1024), jnp.int64, sharding=one_chip)
        compiled = jax.jit(jax.vmap(look)).lower(q, snap).compile()
        temps.append(_check(compiled, kernel=True, max_temp=FIXED_TEMP))
    assert temps[0] == temps[1], temps


def test_facade_lookup_and_join_compile_at_deployment_size(
        one_chip, kernel_dispatch):
    """The serving engine's read sites over a 2^24-row frame: a lookup
    batch at the top of the ladder and a 2^16-row probe join, each
    probe -> chain walk (kernel) -> row gather (XLA)."""
    from repro.frame import IndexedFrame
    frame = IndexedFrame(data=_place(_table_shapes(ROWS), one_chip))
    q = jax.ShapeDtypeStruct((256,), jnp.int64, sharding=one_chip)
    lookup = jax.jit(lambda fr, k: fr.lookup(k, max_matches=4))
    bound = _row_relayout_bytes(ROWS) + FIXED_TEMP
    _check(lookup.lower(frame, q).compile(), kernel=True, max_temp=bound)
    probe = _place({"k": jax.ShapeDtypeStruct((1 << 16,), jnp.int64),
                    "p": jax.ShapeDtypeStruct((1 << 16,), jnp.int32)},
                   one_chip)
    join = jax.jit(lambda fr, pc: fr.join(pc, "k", max_matches=4))
    _check(join.lower(frame, probe).compile(), kernel=True, max_temp=bound)


def test_arena_ingest_compiles_at_deployment_size(one_chip):
    """``_ingest_arrays`` (through the facade's arena append jit) landing
    one 4096-row delta in a 2^24-row table."""
    table = _place(_table_shapes(ROWS), one_chip)
    delta = _place({"k": jax.ShapeDtypeStruct((RB,), jnp.int64),
                    "v": jax.ShapeDtypeStruct((RB,), jnp.float32)},
                   one_chip)
    valid = jax.ShapeDtypeStruct((RB,), jnp.bool_, sharding=one_chip)
    compiled = table_mod._arena_ingest.lower(table, delta, valid).compile()
    _check(compiled, kernel=False)


def test_ring_flush_compiles_at_deployment_size(one_chip):
    """``_flush_core``: a full 8-lane append ring into a 2^24-row table."""
    table = _place(_table_shapes(ROWS), one_chip)
    queue = _place(jax.eval_shape(functools.partial(
        table_mod.empty_queue, SCH, lane_rows=RB)), one_chip)
    tail = table.segments[-1]
    compiled = table_mod._flush.lower(
        table_mod._dedup_state(table), table.snapshot.blocks[:-1], queue,
        schema=SCH, layout="row", rb=tail.row_base,
        bucket_counts=table.snapshot.bucket_counts, slots=SLOTS,
        cap=tail.row_base + tail.capacity).compile()
    _check(compiled, kernel=False)


def test_routed_lookup_compiles_on_four_chip_mesh(topo, kernel_dispatch):
    """The facade's routed lookup on a 4-device shard_map mesh: 2^24 rows
    per shard, exchange -> kernel probe -> exchange."""
    from repro.dist import dtable, mesh
    from repro.frame import IndexedFrame
    rt = mesh.mesh_runtime(4, devices=topo.devices)
    split = NamedSharding(rt.mesh, P(rt.axis))
    whole = NamedSharding(rt.mesh, P())
    table = _place(_table_shapes(ROWS, num_shards=4), split)
    dt = dtable.DistributedTable(
        table=table, num_shards=4,
        version=jax.ShapeDtypeStruct((), jnp.int32, sharding=whole))
    frame = IndexedFrame(data=dt, rt=rt)
    q = jax.ShapeDtypeStruct((4096,), jnp.int64, sharding=whole)
    compiled = jax.jit(lambda fr, keys: fr.lookup(
        keys, max_matches=64, op="routed")).lower(frame, q).compile()
    _check(compiled, kernel=True,
           max_temp=_row_relayout_bytes(ROWS) + FIXED_TEMP)
    assert "all-to-all" in compiled.as_text()


def test_distributed_build_fits_four_chip_mesh(topo):
    """``create_distributed``'s shard_map build at 2^24 rows per shard.
    Hash routing leaves the fullest shard a little over the share; the
    reserved class is still the share's, and the segment build and the
    flat-data snapshot each fit one chip."""
    from repro.dist import dtable, mesh
    cap = dtable.shard_capacity(ROWS, ROWS + 3000, RB)
    assert cap == table_mod.capacity_class(ROWS, RB) == 2 * ROWS
    rt = mesh.mesh_runtime(4, devices=topo.devices)
    split = NamedSharding(rt.mesh, P(rt.axis))
    col = functools.partial(jax.ShapeDtypeStruct, (4, cap), sharding=split)
    build = dtable._segment_build_fn(
        rt, SCH, 0, RB, "row", hashindex.suggest_num_buckets(cap, SLOTS),
        SLOTS)
    args = ({"k": col(jnp.int64), "v": col(jnp.float32)}, col(jnp.bool_),
            col(jnp.int32))
    _check(build.lower(*args).compile(), kernel=False)
    seg = _place(jax.eval_shape(build, *args)[0], split)
    _check(dtable._snapshot_fn(rt, SCH, "row").lower(seg).compile(),
           kernel=False)
