"""The one mesh constructor, and the per-chip peaks table.

Every mesh in the repo is built by :func:`make_mesh` (or its device-subset
form :func:`mesh_over`), which types every axis ``AxisType.Auto``.  JAX
0.9's ``jax.make_mesh`` types axes Explicit by default; the train step is
written for Auto axes (``shard_map`` manual over the dp axes, GSPMD over
``model``), and under Explicit axes the model's sharding constraints and
embedding gathers raise ``ShardingTypeError``.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """Mesh of ``shape`` over axes named ``axes``, every axis Auto.

    ``devices=None`` lays out all visible devices the way
    ``jax.make_mesh`` does; an explicit device list (a described TPU
    topology's ``devices``, an elastic world) is used in the given order.
    """
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    devices = list(devices)
    if int(np.prod(shape)) != len(devices):
        raise ValueError(f"mesh shape {shape} needs {int(np.prod(shape))} "
                         f"devices, got {len(devices)}")
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The 256-chip pod mesh (x2 pods with ``multi_pod``) the dry-run
    compiles for on forced host devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_over(devices, shape, axes) -> Mesh:
    """Mesh over an explicit device subset (elastic worlds, DESIGN.md §12).

    An elastic shrink needs a mesh over just the surviving workers'
    devices, and a regrow one over survivors + joiners in membership rank
    order — so the devices keep exactly the given order.
    """
    return make_mesh(shape, axes, devices=devices)


class ChipPeaks(NamedTuple):
    """Published per-chip peaks for the roofline terms."""
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # bytes/s per ICI link (per-device collective bw)
    hbm_bytes: int        # HBM capacity


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" (system architecture page): 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip over 4 links.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             hbm_bytes=16 * 2**30),
}

# The chip the analytic models (roofline, serve_sim, modeled benchmarks)
# plan for.
V5E = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None
