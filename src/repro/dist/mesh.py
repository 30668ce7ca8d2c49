"""Mesh-native execution backend for the distributed layer.

Every ``repro.dist`` op is written as a *per-shard* function: it sees one
shard's slice of the dtable pytree and may use named-axis collectives
(``lax.psum`` for the owner-select, ``lax.all_to_all`` for the shuffle,
``lax.axis_index`` for ownership tests).  This module owns the ONE seam
that decides how that function is mapped over the shard axis:

* ``backend="vmap"`` — ``jax.vmap(fn, axis_name=...)`` on one device.
  JAX gives every collective a batching rule, so the same psum /
  all_to_all / axis_index code runs unchanged; this is the CPU-CI
  emulation path (and the historical behaviour of the layer).
* ``backend="shard_map"`` — ``jax.shard_map`` over a real 1-D device
  mesh, the shard axis sharded over devices.  The per-shard function now
  runs SPMD: the shuffle's src<->dest transpose is a genuine
  ``lax.all_to_all`` over the interconnect and the owner-select is a
  cross-device ``lax.psum`` (paper §III-C; scalability Fig 6).

The two backends are **bit-identical by construction** — they map the
same per-shard function, and the collectives used move data unchanged
(all_to_all, axis_index); owner-selects are gathers on the stacked
outputs.  One platform caveat: XLA lowers cross-device float combines
(psum / sharded gather / all_gather) as zero-padded sums, so stored
float ``-0.0`` crossing shards in the broadcast select canonicalizes to
``+0.0`` (numerically equal; the packed all_to_all paths are bit-exact
for every payload — see DESIGN.md §10).
``tests/test_mesh_parity.py`` locks parity down op by op.

Collective mapping (vmap <-> shard_map):

  per-shard code               vmap backend          shard_map backend
  ---------------------------  --------------------  --------------------
  ``lax.axis_index(axis)``     batching rule (iota)  device's mesh coord
  ``lax.all_to_all`` shuffle   transpose-in-lane     ICI/DCN all-to-all
  ``lax.psum`` sums/counts     sum over stacked axis cross-device psum
  ``lax.ppermute`` rotations   gather permutation    neighbour exchange

CPU CI gets a real multi-device mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/ci.sh
runs the suite under both topologies).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "shards"


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Backend selector for the shard axis (the dist-layer 'mesh config').

    ``backend`` is ``"vmap"`` (single-device emulation, the default) or
    ``"shard_map"`` (SPMD over ``mesh``).  ``axis`` names the shard axis
    for collectives under either backend.
    """

    backend: str = "vmap"
    mesh: object = None       # jax.sharding.Mesh when backend == "shard_map"
    axis: str = AXIS

    @property
    def num_devices(self) -> int | None:
        return None if self.mesh is None else int(self.mesh.shape[self.axis])

    def check(self, num_shards: int):
        """Raise early if this runtime cannot map ``num_shards`` shards."""
        if self.backend == "shard_map" and self.num_devices != num_shards:
            raise ValueError(
                f"shard_map runtime has a {self.num_devices}-device mesh "
                f"but the dtable has {num_shards} shards; build it with "
                f"mesh_runtime({num_shards})")
        return self


def vmap_runtime(axis: str = AXIS) -> Runtime:
    """The single-device emulation backend (collectives via vmap rules)."""
    return Runtime(backend="vmap", mesh=None, axis=axis)


def mesh_runtime(num_shards: int, *, devices=None,
                 axis: str = AXIS) -> Runtime:
    """A shard_map backend over a 1-D mesh of ``num_shards`` devices.

    ``devices`` defaults to the first ``num_shards`` of
    ``jax.devices()``; CPU CI forces eight with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < num_shards:
        raise ValueError(
            f"need {num_shards} devices for a {num_shards}-shard mesh, "
            f"have {len(devices)} (CPU: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards})")
    mesh = jax.sharding.Mesh(np.asarray(devices[:num_shards]), (axis,))
    return Runtime(backend="shard_map", mesh=mesh, axis=axis)


def place(tree, rt: Runtime | None, *, stacked: bool = True):
    """Put a pytree where the runtime keeps it — THE placement seam for
    everything built outside ``axis_map`` (a fresh dtable, an empty append
    ring, a tracker, a hot-key replica).

    shard_map backend: a ``stacked`` tree (every leaf carries the leading
    ``[num_shards]`` axis — a table, a ring, a tracker) is split with
    ``NamedSharding(mesh, P(axis))``, shard ``i``'s slice on device ``i``;
    any other tree (the dtable's global version, the replica) is
    replicated over the mesh.  Placement follows the caller's structure,
    never a leaf's shape.  These are the shardings the axis-mapped jits
    hand back, so a jitted read site sees the same input shardings before
    and after a write and never retraces on placement.  vmap backend:
    unchanged.
    """
    rt = resolve(rt)
    if rt.backend != "shard_map":
        return tree
    to = NamedSharding(rt.mesh, P(rt.axis) if stacked else P())
    return jax.tree.map(lambda x: jax.device_put(x, to), tree)


def place_like(tree, like):
    """Put ``tree`` on the shardings of ``like``, an already placed pytree
    of the same structure — a restored checkpoint, a blanked or spliced
    shard lands exactly where its template lives.  Leaves of a vmap-backend
    template (one device) pass through unchanged."""

    def put(x, t):
        s = getattr(t, "sharding", None)
        return jax.device_put(x, s) if isinstance(s, NamedSharding) else x

    return jax.tree.map(put, tree, like)


def resolve(rt: Runtime | None) -> Runtime:
    """None -> the default vmap backend (back-compat for every call site)."""
    return rt if rt is not None else vmap_runtime()


def axis_map(fn, rt: Runtime | None, in_axes=0):
    """Map a per-shard function over the leading shard axis — THE seam.

    ``fn`` takes per-shard pytrees (no shard axis) and may use collectives
    over ``rt.axis``; every output grows a leading ``[num_shards]`` axis.
    ``in_axes`` is 0 (sharded on axis 0) or ``None`` (replicated to every
    shard), a single value or one per positional argument — the same
    contract as ``jax.vmap``'s, restricted to {0, None}.

    vmap backend: exactly ``jax.vmap(fn, in_axes, axis_name=rt.axis)``.
    shard_map backend: ``in_axes=0`` becomes ``P(axis)`` (leaf rows live
    on their shard's device), ``None`` becomes ``P()`` (replicated); the
    per-device block keeps a leading axis of size 1, which the wrapper
    squeezes on the way in and restores on the way out so ``fn`` sees the
    same shapes under both backends.
    """
    rt = resolve(rt)
    if rt.backend == "vmap":
        return jax.vmap(fn, in_axes=in_axes, axis_name=rt.axis)
    if rt.backend != "shard_map":
        raise ValueError(f"unknown dist backend {rt.backend!r}")

    def mapped(*args):
        axes = (tuple(in_axes) if isinstance(in_axes, (tuple, list))
                else (in_axes,) * len(args))
        if len(axes) != len(args):
            raise ValueError(f"in_axes {axes} vs {len(args)} arguments")
        in_specs = tuple(P(rt.axis) if a == 0 else P() for a in axes)

        def blocked(*blocks):
            local = tuple(
                jax.tree.map(lambda x: x[0], b) if a == 0 else b
                for a, b in zip(axes, blocks))
            out = fn(*local)
            return jax.tree.map(lambda x: x[None], out)

        return jax.shard_map(blocked, mesh=rt.mesh, in_specs=in_specs,
                             out_specs=P(rt.axis), check_vma=False)(*args)

    return mapped
