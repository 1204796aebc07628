"""Production meshes for the TPU v5e target.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, while tests and benchmarks see the single real CPU device.
"""
from __future__ import annotations

import jax

from repro.dist.sharding import MeshAxes

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over four ICI links
# (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 50e9},
}
# the chip the production meshes below are laid out for
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Published peaks of one ``device_kind`` chip. A kind missing from
    ``CHIP_PEAKS`` is an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)})") from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def mesh_axes(multi_pod: bool = False) -> MeshAxes:
    return MeshAxes(pod="pod") if multi_pod else MeshAxes()


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the host devices (tests / CPU examples)."""
    return jax.make_mesh((data, model), ("data", "model"))
