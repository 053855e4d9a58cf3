"""Production mesh builders.

``make_production_mesh()`` is a *function* (not module-level state) so
importing this module never touches jax device state. The single-pod mesh
is 16x16 = 256 chips (TPU v5e pod); multi-pod adds a leading ``pod`` axis
(2 pods = 512 chips) that carries pure data parallelism over DCN — the
modern analogue of the paper's inter-cluster "edge nodes" (§V-F).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(devices, axis_names):
    """``jax.sharding.Mesh`` over ``devices`` with Auto axis types."""
    return Mesh(devices, axis_names, axis_types=(AxisType.Auto,) * len(axis_names))


def _mesh(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()
    assert len(devs) >= n, f"need {n} devices, have {len(devs)} (set XLA_FLAGS)"
    return make_mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return _mesh((data, model), ("data", "model"))


def make_band_mesh(n_devices: int = 0):
    """1-D ``(band,)`` mesh for the distributed TOP-ILU pipeline
    (DESIGN.md §5). ``n_devices=0`` takes every available device; bands are
    owned round-robin over this axis (paper §IV-D) and the factorization
    value state is sharded along it."""
    d = n_devices or len(jax.devices())
    return _mesh((d,), ("band",))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_size(mesh) -> int:
    s = mesh_axis_sizes(mesh)
    return s.get("data", 1) * s.get("pod", 1)


def tp_size(mesh) -> int:
    return mesh_axis_sizes(mesh).get("model", 1)
