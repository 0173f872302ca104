"""Production mesh construction.

Importing this module never touches jax device state — meshes are built
inside functions only, so the 512-placeholder-device XLA flag (set by
``dryrun.py`` before any jax import) and real-TPU runs both work.

Topology: one v5e pod = 16×16 = 256 chips → mesh ("data", "model").
Multi-pod adds a leading "pod" axis (DCN-connected): batch shards over
("pod", "data"); "model" (TP/EP) stays inside a pod where ICI is fast.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def _make_mesh(shape, axes, devices):
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes, jax.devices()[: int(np.prod(shape))])


def make_host_mesh(shape: Tuple[int, ...] = None, axes=None):
    """Small mesh over whatever devices exist (tests / local runs)."""
    import jax

    n = len(jax.devices())
    if shape is None:
        shape, axes = (n, 1), ("data", "model")
    return _make_mesh(shape, axes, jax.devices()[: int(np.prod(shape))])


def make_serve_mesh(n_slots: Optional[int] = None, *, model: int = 1):
    """DP-majority serve mesh over the host's devices (DESIGN.md §7).

    The engine's slot axis is the data-parallel dimension, so the "data"
    axis is the largest power of two that (a) fits the devices left after
    the requested "model" (TP) axis and (b) divides ``n_slots`` — a data
    axis that does not divide the slot count would make
    ``serve_state_pspecs`` fall back to replication. One device yields
    the degenerate (1, 1) mesh; the 8-fake-device CI host
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) with 8
    slots yields (8, 1)."""
    import jax

    n = len(jax.devices()) // max(int(model), 1)
    d = 1
    while d * 2 <= n and (n_slots is None or int(n_slots) % (d * 2) == 0):
        d *= 2
    return make_host_mesh((d, int(model)), ("data", "model"))


# Hardware constants for the roofline (TPU v5e per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
