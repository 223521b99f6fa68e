"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the 1-device CPU default.

Mesh shapes:
  single pod : (16, 16)     axes ("data", "model")   — 256 chips (v5e pod)
  multi pod  : (2, 16, 16)  axes ("pod", "data", "model") — 512 chips

Batch shards over ("pod", "data"); params FSDP over "data" (+"pod" when
``fsdp_over_pod``) composed with TP/EP over "model".
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    # Auto axes: the train/dry-run paths shard by NamedSharding + GSPMD
    # propagation, not the Explicit-axis typing jax.make_mesh now defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(mesh) -> dict:
    names = mesh.axis_names
    multi = "pod" in names
    return {
        "dp": ("pod", "data") if multi else ("data",),
        "fsdp": ("data",),
        "fsdp_pod": ("pod", "data") if multi else ("data",),
        "tp": "model",
        "multi_pod": multi,
    }


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(1, data)))
    return make_mesh((data, model), ("data", "model"))
