"""Production meshes.

Single pod: (16, 16) = ("data", "model") — 256 chips (TPU v5e-256 pod).
Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips across 2 pods;
the "pod" axis carries cross-pod data parallelism over the slower DCI links.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; callers control when devices are
initialized (the dry-run sets XLA_FLAGS for 512 host devices first).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over whatever devices exist (tests on 1-8 CPU devices)."""
    devices = jax.devices()[: data * model]
    return Mesh(np.asarray(devices).reshape(data, model), ("data", "model"))


def make_cell_mesh(n_devices: int | None = None, axis: str = "cells") -> Mesh:
    """1-D mesh carrying the fleet's cell axis (closed-loop engine sharding).

    Unlike the 2-D serving meshes above, the fleet program has exactly one
    parallel dimension — R independent service cells — so the mesh is a flat
    device list under a single named axis.  ``n_devices=None`` takes every
    local device (the ``shard="auto"`` default of
    :class:`repro.api.shard.ShardSpec`); CI builds a virtual 4-way CPU mesh
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
    """
    devices = jax.local_devices()
    n = len(devices) if n_devices is None else n_devices
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for a {n}-way cell mesh, have {len(devices)} "
            "— run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n}")
    return Mesh(np.asarray(devices[:n]), (axis,))
