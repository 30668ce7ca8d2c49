"""Pallas TPU kernel for the fused hash-index lookup — the paper's hot path.

The paper's Fig 1 observation is that the *probe* amortizes: the index is
built once and probed millions of times (point lookups, join probes).  One
kernel serves every probe: per query, the first matching head pointer over
a Snapshot's segment planes (newest -> oldest), then the backward-pointer
chain walk over the flat ``prev`` array (DESIGN.md §7).  A single-index
probe is the one-segment, ``max_matches=1`` case (``ops.probe``).

Memory placement (TPU v5e: VMEM and SMEM are MiB, a table is GB):
  * the bucket planes and ``prev`` stay in HBM (``memory_space=pl.ANY``);
    nothing of size O(capacity) is mapped into VMEM, so VMEM use is fixed
    by the tile and the slot count, not by the table;
  * per-query scalars (bucket ids, key hi/lo, ``fill``) come in SMEM
    blocks, one query tile per grid step; the outputs are SMEM blocks
    written one scalar at a time — no scalar is ever stored to VMEM;
  * the planes are passed transposed, ``[slots, nb]``.  XLA stores a
    ``[nb, slots]`` int32 plane slot-major on TPU, so the transpose is a
    bitcast (no copy), and the ``(slots, 128)`` window holding bucket
    ``b`` is one tile-aligned DMA.  ``prev`` is passed as stored, ``[T,
    cap]``, and a hop fetches the 128-lane column window holding the
    pointer for all ``T`` tables — one tile, since a ``[T, cap]`` array is
    tiled ``(T, 128)`` for small ``T``; reshaping it to ``[T, cap/128,
    128]`` instead would relayout it on every call.

Per grid step (a tile of at most QUERY_TILE queries):
  1. for each segment, newest -> oldest: one DMA per plane per query whose
     head is still NULL, all in flight together, then a masked vector
     compare per query reduces the window to the candidate pointer;
  2. the head is fill-masked, then ``max_matches`` hops: each hop writes
     the current row id, and queries still on a chain fetch their ``prev``
     row (all in flight together) and take the fill-masked next pointer.
     A hop in which every chain has ended issues no DMA.
The would-be next pointer after the last hop is returned so the wrapper
can flag truncation.  Bit-identical to ``ref.fused_lookup_ref``.

The grid has a leading table axis: the distributed layer's vmap runtime
stacks shard tables, and Pallas cannot batch an HBM (``pl.ANY``) operand
itself, so ``fused_lookup_tiles`` carries a ``custom_vmap`` rule that maps
a stacked snapshot onto that axis (an unbatched operand is broadcast to
the batch).

TPU adaptation notes:
  * int64 keys are pre-split into (hi, lo) int32 planes — the TPU VPU has no
    64-bit lanes; two int32 compares AND'd give the exact equality test.
  * bucket ids are computed in the XLA wrapper (ops.py) — the splitmix mix
    uses 64-bit multiplies which belong on the XLA side.
  * slot resolution is branch-free: ``max(where(match, ptr, NULL))`` — valid
    pointers are >= 0 and NULL is -1, so a vector max replaces argmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime

QUERY_TILE = 256                # callers pad query counts to this
LANES = 128
NULL = -1
_PROBE, _HOP = 0, 1             # the two DMA semaphores
# SMEM is 1 MiB on v5e; the double-buffered rows window (tile * M int32)
# stays within half of it, so the tile shrinks as max_matches grows.
_ROWS_WINDOW = 1 << 16


def _tile_for(max_matches: int) -> int:
    """Queries per grid step: QUERY_TILE, halved until the rows window
    fits; always a power of two dividing QUERY_TILE."""
    if max_matches > _ROWS_WINDOW:
        raise ValueError(f"max_matches={max_matches} exceeds the kernel's "
                         f"{_ROWS_WINDOW}-row SMEM window")
    tile = QUERY_TILE
    while tile > 1 and tile * max_matches > _ROWS_WINDOW:
        tile //= 2
    return tile


def _lane_pick(window, lane, extra=None):
    """Max over the window's column ``lane`` (NULL-filled elsewhere),
    optionally ANDed with a per-element mask: a vector -> scalar reduce."""
    cols = jax.lax.broadcasted_iota(jnp.int32, window.shape, window.ndim - 1)
    keep = cols == lane
    if extra is not None:
        keep = keep & extra
    return jnp.max(jnp.where(keep, window, NULL))


def _fused_lookup_kernel(*refs, num_segments: int, max_matches: int,
                         prev_rows: int, tile: int):
    """One grid step: table ``t``, a tile of queries, every segment.

    refs: bids (SMEM [S * tile], segment-major), qhi, qlo (SMEM [tile]),
    fill (SMEM [1]); per segment the (hi, lo, ptr) planes ``[T, slots,
    nb]`` and ``prev`` ``[T, prev_rows * 128]`` (all HBM); outputs rows
    (SMEM [tile * M], query-major) and last (SMEM [tile]); scratch: the
    plane windows (VMEM [3, tile, slots, 128]), the prev windows (VMEM
    [tile, T, 128]), the per-query chain cursor (SMEM [tile]) and two DMA
    semaphores.
    """
    bids_ref, qhi_ref, qlo_ref, fill_ref = refs[:4]
    planes = refs[4:4 + 3 * num_segments]
    prev_hbm = refs[4 + 3 * num_segments]
    rows_ref, last_ref, win, pwin, cur, sem = refs[5 + 3 * num_segments:]
    t = pl.program_id(0)
    fill = fill_ref[0, 0]

    def each_query(body):
        def step(j, carry):
            body(j)
            return carry
        jax.lax.fori_loop(0, tile, step, None)

    # -- 1. probe: first non-NULL candidate newest -> oldest -----------------
    def init(j):
        cur[j] = NULL
    each_query(init)

    for s in range(num_segments - 1, -1, -1):
        seg_planes = planes[3 * s:3 * s + 3]

        def plane_copy(j, p, src, s=s):
            b = bids_ref[0, s * tile + j]
            base = pl.multiple_of((b // LANES) * LANES, LANES)
            return pltpu.make_async_copy(src.at[t, :, pl.ds(base, LANES)],
                                         win.at[p, j], sem.at[_PROBE])

        def fetch(j, plane_copy=plane_copy, seg_planes=seg_planes):
            @pl.when(cur[j] == NULL)
            def _():
                for p, src in enumerate(seg_planes):
                    plane_copy(j, p, src).start()

        def compare(j, plane_copy=plane_copy, seg_planes=seg_planes, s=s):
            @pl.when(cur[j] == NULL)
            def _():
                for p, src in enumerate(seg_planes):
                    plane_copy(j, p, src).wait()
                hi, lo, ptr = (win[p, j] for p in range(3))
                match = (hi == qhi_ref[0, j]) & (lo == qlo_ref[0, j])
                cur[j] = _lane_pick(ptr, bids_ref[0, s * tile + j] % LANES,
                                    match)

        each_query(fetch)
        each_query(compare)

    def mask_head(j):
        h = cur[j]
        cur[j] = jnp.where(h < fill, h, NULL)          # fill-masked head
    each_query(mask_head)

    # -- 2. chain walk over the flat prev array -----------------------------
    def prev_copy(j):
        row = jnp.minimum(cur[j] // LANES, prev_rows - 1)
        start = pl.multiple_of(row * LANES, LANES)
        return pltpu.make_async_copy(
            prev_hbm.at[:, pl.ds(start, LANES)], pwin.at[j], sem.at[_HOP])

    tables = jax.lax.broadcasted_iota(jnp.int32, pwin.shape[1:], 0)

    def hop(m, alive):
        def emit(j):
            c = cur[j]
            rows_ref[0, j * max_matches + m] = c

            @pl.when(c >= 0)
            def _():
                prev_copy(j).start()
        each_query(emit)

        def advance(j, n_alive):
            c = cur[j]

            @pl.when(c >= 0)
            def _():
                prev_copy(j).wait()
                nxt = _lane_pick(pwin[j], c % LANES, tables == t)
                cur[j] = jnp.where(nxt < fill, nxt, NULL)  # fill-masked hop
            return n_alive + (cur[j] >= 0).astype(jnp.int32)

        return jax.lax.cond(alive > 0,
                            lambda: jax.lax.fori_loop(0, tile, advance, 0),
                            lambda: 0)

    jax.lax.fori_loop(0, max_matches, hop, tile)

    def finish(j):
        last_ref[0, j] = cur[j]
    each_query(finish)


def _stacked_call(bids, qhi, qlo, fill, planes, prev, *, max_matches: int,
                  interpret: bool):
    """The kernel over ``T`` stacked tables.

    bids [T, S, Q], qhi/qlo [T, Q], fill [T], planes: 3 per segment, each
    [T, nb_s, slots], prev [T, cap] -> (rows [T, Q, M], last [T, Q]).
    """
    n_tab, s, q = bids.shape
    tile = _tile_for(max_matches)
    nt = q // tile

    plane_args = []
    for plane in planes:
        nb = plane.shape[1]
        if nb % LANES:                 # small tables: pad to one lane tile
            plane = jnp.pad(plane, ((0, 0), (0, LANES - nb % LANES), (0, 0)),
                            constant_values=NULL)
        plane_args.append(plane.transpose(0, 2, 1))   # a bitcast on TPU
    slots = plane_args[0].shape[1]
    if prev.shape[1] % LANES:
        prev = jnp.pad(prev, ((0, 0), (0, LANES - prev.shape[1] % LANES)),
                       constant_values=NULL)
    # Per-tile SMEM operands are [T, nt, 1, width] with squeezed table and
    # tile axes: the (1, width) block spans the last two dims, as Mosaic
    # needs for SMEM blocks narrower than a lane tile.
    bids = (bids.reshape(n_tab, s, nt, tile).transpose(0, 2, 1, 3)
            .reshape(n_tab, nt, 1, s * tile))        # per tile: segment-major

    def smem(width):
        return pl.BlockSpec((None, None, 1, width), lambda b, i: (b, i, 0, 0),
                            memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    qspec = smem(tile)
    kernel = functools.partial(_fused_lookup_kernel, num_segments=s,
                               max_matches=max_matches,
                               prev_rows=prev.shape[1] // LANES, tile=tile)
    # The kernel is traced with x64 off: under x64 its Python-int indices
    # and loop bounds would lower as int64, which Mosaic rejects.
    with jax.enable_x64(False):
        rows, last = pl.pallas_call(
            kernel,
            grid=(n_tab, nt),
            in_specs=[smem(s * tile), qspec, qspec,
                      pl.BlockSpec((None, 1, 1), lambda b, i: (b, 0, 0),
                                   memory_space=pltpu.SMEM),
                      *[hbm] * len(plane_args), hbm],
            out_specs=(smem(tile * max_matches), qspec),
            out_shape=(
                jax.ShapeDtypeStruct((n_tab, nt, 1, tile * max_matches),
                                     jnp.int32),
                jax.ShapeDtypeStruct((n_tab, nt, 1, tile), jnp.int32)),
            scratch_shapes=[
                pltpu.VMEM((3, tile, slots, LANES), jnp.int32),
                pltpu.VMEM((tile, n_tab, LANES), jnp.int32),
                pltpu.SMEM((tile,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret,
        )(bids, qhi.reshape(n_tab, nt, 1, tile),
          qlo.reshape(n_tab, nt, 1, tile),
          jnp.asarray(fill, jnp.int32).reshape(n_tab, 1, 1),
          *plane_args, prev)
    return rows.reshape(n_tab, q, max_matches), last.reshape(n_tab, q)


@functools.lru_cache(maxsize=None)
def _lookup_fn(max_matches: int, interpret: bool):
    """The one-table kernel call, with its vmap rule, per static config."""

    @jax.custom_batching.custom_vmap
    def lookup(bids, qhi, qlo, fill, planes, prev):
        rows, last = _stacked_call(
            bids[None], qhi[None], qlo[None], fill[None],
            tuple(p[None] for p in planes), prev[None],
            max_matches=max_matches, interpret=interpret)
        return rows[0], last[0]

    @lookup.def_vmap
    def _batched(n, in_batched, bids, qhi, qlo, fill, planes, prev):
        def lead(x, batched):
            return x if batched else jnp.broadcast_to(x, (n,) + x.shape)

        b_bids, b_qhi, b_qlo, b_fill, b_planes, b_prev = in_batched
        bids, qhi, qlo = (lead(x, b) for x, b in
                          ((bids, b_bids), (qhi, b_qhi), (qlo, b_qlo)))
        out = _stacked_call(
            bids, qhi, qlo, lead(fill, b_fill),
            tuple(lead(p, b) for p, b in zip(planes, b_planes)),
            lead(prev, b_prev), max_matches=max_matches, interpret=interpret)
        return out, (True, True)

    return lookup


@functools.partial(jax.jit, static_argnames=("max_matches", "interpret"))
def fused_lookup_tiles(bucket_ids, q_hi, q_lo, snapshot,
                       *, max_matches: int, interpret: bool | None = None):
    """Fused probe + chain walk over a table's stored Snapshot.

    bucket_ids : [S, Q] int32  per-segment bucket ids (Q padded to tile)
    q_hi/q_lo  : [Q] int32     query key planes
    snapshot   : core.snapshot.Snapshot — ragged per-segment (hi, lo, ptrs)
                 planes (each [nb_s, slots] int32), the flat [capacity]
                 int32 backward-pointer array and the ``fill`` scalar; a
                 registered pytree, so this jit caches on its structure
                 (bucket_counts ride in the treedef)
    returns    : (rows [Q, max_matches] int32 newest-first NULL-padded,
                  last [Q] int32 — next row id after the walk; >= 0 means
                  the chain was truncated at max_matches)

    VMEM holds ``tile * (3 * slots + max(T, 8)) * 128 * 4`` bytes of
    windows for ``T`` stacked tables (1 unless vmapped), whatever the
    tables' size; SMEM holds the query tile and a
    ``tile * max_matches`` output block (``_tile_for``).
    """
    assert bucket_ids.shape[1] % QUERY_TILE == 0, bucket_ids.shape
    assert len(snapshot.key_planes) == bucket_ids.shape[0]
    planes = tuple(p for triple in snapshot.key_planes for p in triple)
    fn = _lookup_fn(max_matches, runtime.resolve_interpret(interpret))
    return fn(bucket_ids, q_hi, q_lo, snapshot.fill, planes, snapshot.prev)
