"""Mesh construction — the one mesh-building path.

Every mesh in the repo (the partitioned-inference ``nodes`` mesh of the
mesh executor, the small local test meshes, the production TPU meshes of
the dry-run) is built through :func:`_grid`, which validates the device
count and raises an actionable error naming the ``XLA_FLAGS`` host-device
override when the host platform is short of devices.

Functions (not module-level constants) so importing this module never
touches jax device state — callers set ``XLA_FLAGS`` (e.g.
``--xla_force_host_platform_device_count=8``) before any jax use and the
first ``jax.devices()`` call here sees it.
"""
from __future__ import annotations

import math
import os
import re
from typing import Optional, Sequence, Tuple


def requested_host_devices() -> Optional[int]:
    """Host-device count requested via ``XLA_FLAGS``, if any.  Parsed from
    the environment (not from jax) so it reflects what *was asked for* even
    when jax initialized before the flag was set."""
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else None


def _grid(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """Build a mesh of ``shape`` over the first ``prod(shape)`` devices."""
    import jax

    n = math.prod(shape)
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) < n:
        req = requested_host_devices()
        hint = (f"XLA_FLAGS requested {req} host devices but jax "
                f"initialized before the flag was set"
                if req is not None and req >= n else
                f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n} before importing jax to fake host devices")
        raise RuntimeError(
            f"mesh {axes}={shape} needs {n} devices, found {len(devs)} "
            f"({hint})")
    # the executors place data with shard_map and plain jit, which is what
    # Auto axes give; make_mesh's default (Explicit) axes refuse host-side
    # indexing of arrays sharded over them
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, auto, devices=devs[:n])


def make_nodes_mesh(nodes: int, devices: Optional[Sequence] = None):
    """1-D mesh over the planned edge nodes — the mesh executor's axis.

    One device per plan node; CPU CI fakes the devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    if nodes < 1:
        raise ValueError(f"nodes must be >= 1, got {nodes}")
    return _grid((nodes,), ("nodes",), devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _grid(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    import jax

    n = len(jax.devices())
    assert n % model_axis == 0
    return _grid((n // model_axis, model_axis), ("data", "model"))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
