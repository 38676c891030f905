"""Production meshes.

make_production_mesh() builds the required (data=16, model=16) single-pod /
(pod=2, data=16, model=16) multi-pod mesh. Architectures factor the model axis
into stage x tp; make_logical_mesh() re-views the SAME device order with the
model axis split — tp groups are ICI-adjacent (innermost), stages next, so
high-traffic TP collectives ride the fastest links.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh_auto(shape, names):
    """jax.make_mesh with every axis Auto (GSPMD propagates shardings)."""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))


_make = make_mesh_auto


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_logical_mesh(prod: Mesh, stages: int, tp: int) -> Mesh:
    """Split the production mesh's 16-wide `model` axis into (stage, tp),
    preserving physical device order (tp innermost = ICI-adjacent)."""
    model = prod.shape["model"]
    assert stages * tp == model, (stages, tp, model)
    names = list(prod.axis_names)
    devs = np.asarray(prod.devices)
    new_shape = devs.shape[:-1] + (stages, tp)
    new_names = tuple(names[:-1]) + ("stage", "tp")
    return Mesh(devs.reshape(new_shape), new_names,
                axis_types=(AxisType.Auto,) * len(new_names))


def make_test_mesh(data=2, stages=2, tp=2) -> Mesh:
    """Small logical mesh for CPU multi-device tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=data*stages*tp)."""
    return _make((data, stages, tp), ("data", "stage", "tp"))
