#!/usr/bin/env python3
"""Compile each cell's read site (and the ring flush) for a described TPU
v5e at the cell's real size, and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/fit.py [config ...]

Nothing runs: the programs are lowered against ``ShapeDtypeStruct``
arguments on a described ``v5e:2x2`` topology and compiled with the TPU
compiler that ships with libtpu, which refuses a program that does not
fit the chip's memory.  The lookup dispatcher is steered onto the
compiled Pallas kernel, as on a TPU backend.
"""

from __future__ import annotations

import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def table_shapes(schema, rows: int):
    """The abstract single-chip table ``from_columns`` builds."""
    import jax
    import jax.numpy as jnp
    from repro.core import hashindex, snapshot as snap_mod
    from repro.core import table as table_mod
    from repro.core.table import IndexedTable
    rb, slots = 4096, hashindex.DEFAULT_SLOTS
    cap = table_mod.capacity_class(rows, rb)
    nb = hashindex.suggest_num_buckets(cap, slots)

    def build(cols, valid, heads):
        seg, _ = table_mod.make_segment_arrays(
            cols, valid, heads, schema, row_base=0, rows_per_batch=rb,
            layout="row", num_buckets=nb, slots=slots)
        return seg, snap_mod.snapshot_from_segments((seg,), "row",
                                                    schema=schema)

    sds = jax.ShapeDtypeStruct
    cols = {c.name: sds((cap,), c.jnp_dtype) for c in schema.columns}
    seg, snap = jax.eval_shape(build, cols, sds((cap,), jnp.bool_),
                               sds((cap,), jnp.int32))
    return IndexedTable(segments=(seg,), snapshot=snap,
                        version=sds((), jnp.int32), schema=schema,
                        rows_per_batch=rb, layout="row", slots=slots)


def report(what: str, compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({
        "site": what, "kernel": "tpu_custom_call" in compiled.as_text(),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes, "total_bytes": total}),
        flush=True)


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import Schema
    from repro.core import table as table_mod
    from repro.frame import IndexedFrame
    from repro.kernels import runtime

    jax.config.update("jax_enable_compilation_cache", False)
    runtime.resolve_interpret = (lambda interpret: False if interpret is None
                                 else bool(interpret))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        if names and w["config"] not in names:
            continue
        entry = next(c for c in spec["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        with open(os.path.join(HERE, "mixes", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        schema = Schema.of(cfg["key"], **dict(cfg["schema"]))
        table = place(table_shapes(schema, int(cfg["rows"])))
        mm = int(mix["max_matches"])
        if mix["loop"] == "open_loop":
            queue = place(jax.eval_shape(functools.partial(
                table_mod.empty_queue, schema, lane_rows=4096)))
            frame = IndexedFrame(data=table, queue=queue)
            q = jax.ShapeDtypeStruct((int(mix["max_bucket"]),), jnp.int64,
                                     sharding=chip)
            site = jax.jit(lambda fr, k: fr.lookup(k, max_matches=mm))
            report(f"{w['name']}: lookup site",
                   site.lower(frame, q).compile())
            tail = table.segments[-1]
            report(f"{w['name']}: ring flush", table_mod._flush.lower(
                table_mod._dedup_state(table), table.snapshot.blocks[:-1],
                queue, schema=schema, layout="row", rb=tail.row_base,
                bucket_counts=table.snapshot.bucket_counts,
                slots=table.slots,
                cap=tail.row_base + tail.capacity).compile())
        else:
            frame = IndexedFrame(data=table)
            n = int(mix["probe_rows"])
            probe = place({c: jax.ShapeDtypeStruct(
                (n,), jnp.int32 if t == "int32" else jnp.int64)
                for c, t in cfg["probe"]["schema"]})
            on = cfg["probe"]["on"]
            site = jax.jit(lambda fr, pc: fr.join(pc, on, max_matches=mm))
            report(f"{w['name']}: join site (as one program)",
                   site.lower(frame, probe).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
