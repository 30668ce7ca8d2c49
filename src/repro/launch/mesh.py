"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run script sets
XLA_FLAGS before any jax init; tests that import this module on the single
real CPU device are unaffected.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis
composes with ``data`` as the batch/ZeRO super-axis (gradients all-reduce
hierarchically: fast ICI within a pod, DCN between pods — which is why
grad compression targets the pod axis, train/compress.py).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_spec(spec: str):
    """'16x16' -> (data, model); '2x16x16' -> (pod, data, model).

    Small variants ('2x2', '1x2x2') drive the subprocess tests.
    """
    dims = tuple(int(x) for x in spec.split("x"))
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def data_axes(mesh) -> tuple:
    """The batch super-axis for this mesh ('pod' composes with 'data')."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def hardware_constants():
    """TPU v5e-class target (per chip)."""
    return {
        "peak_flops_bf16": 197e12,     # FLOP/s
        "hbm_bandwidth": 819e9,        # B/s
        "ici_bandwidth": 50e9,         # B/s per link
        "hbm_bytes": 16 * 2**30,
    }
