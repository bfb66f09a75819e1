"""Production mesh definitions (TPU v5e target).

Single pod: 16 × 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 × 16 × 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with "data" for hierarchical gradient reduction
(reduce-scatter intra-pod over ICI, all-reduce across pods over DCI).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS *before* the first jax call).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

# v5e hardware constants used by the roofline (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
HBM_BYTES = 16 * 1024**3  # 16 GiB per chip


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    JAX's default is ``Explicit`` axes, on which ``with_sharding_constraint``
    refuses the logical-axis constraints the models place
    (``parallel.sharding.shard``); every mesh of this repo is built here.
    """
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, n_devices: Optional[int] = None):
    """A ("data", "model") mesh over this host's first ``n_devices``
    (default: all of them): (n/model, model)."""
    devices = jax.devices()[:n_devices]
    n = len(devices)
    assert n % model == 0, (n, model)
    return auto_mesh((n // model, model), ("data", "model"), devices=devices)


def mesh_num_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
