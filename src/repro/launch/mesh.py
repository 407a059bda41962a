"""Production mesh construction.

Defined as a FUNCTION (not a module-level constant) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, while smoke tests must keep seeing 1 device.

Mesh shapes per the assignment:
  single-pod : (16, 16)      axes ("data", "model")        — 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Axis roles:
  pod   — pod-level data parallelism (gradient all-reduce crosses DCN/ICI
          once per step; serving shards the request stream here)
  data  — in-pod data parallel + FSDP parameter sharding
  model — tensor/expert/vocab parallel (+ KV-head-dim sharding for decode)
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType


def _axis_kw(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n // 2, 2) if n % 2 == 0 and n > 1 else (n, 1)
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def all_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    out = 1
    for n in names:
        out *= mesh.shape[n]
    return out
