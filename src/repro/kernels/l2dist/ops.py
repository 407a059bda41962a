"""Jitted public wrappers for the l2dist kernels.

Handles: lane-width padding (d → multiple of 128), INVALID_ID clamping and
masking, interpret-mode fallback on CPU, and an env/flag escape hatch to the
pure-jnp reference (``use_ref=True``) so higher layers can A/B the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .l2dist import batched_l2_pallas, gather_l2_pallas, gather_l2_tiled_pallas

_LANE = 128


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_lane(x: jax.Array, axis: int) -> jax.Array:
    d = x.shape[axis]
    pad = (-d) % _LANE
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("use_ref", "interpret"))
def batched_l2(rows: jax.Array, queries: jax.Array, use_ref: bool = False,
               interpret: bool | None = None) -> jax.Array:
    """rows f32[B, M, d], queries f32[B, d] → squared L2 f32[B, M]."""
    if use_ref:
        return ref.batched_l2_ref(rows, queries)
    interp = _on_cpu() if interpret is None else interpret
    rows_p = _pad_lane(rows, 2)
    q_p = _pad_lane(queries, 1)
    return batched_l2_pallas(rows_p, q_p, interpret=interp)


@functools.partial(jax.jit, static_argnames=("use_ref", "interpret"))
def gather_l2(base: jax.Array, ids: jax.Array, queries: jax.Array,
              use_ref: bool = False, interpret: bool | None = None) -> jax.Array:
    """base f32[n, d], ids int32[B, M] (INVALID→+inf), queries f32[B, d]."""
    safe = jnp.maximum(ids, 0)
    if use_ref:
        d2 = ref.gather_l2_ref(base, safe, queries)
    else:
        interp = _on_cpu() if interpret is None else interpret
        d2 = gather_l2_pallas(_pad_lane(base, 1), safe, _pad_lane(queries, 1),
                              interpret=interp)
    return jnp.where(ids >= 0, d2, jnp.inf)


@functools.partial(jax.jit, static_argnames=("use_ref", "interpret"))
def gather_l2_tiled(base: jax.Array, ids: jax.Array, queries: jax.Array,
                    use_ref: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """Tiled fused gather+L2: one grid step per block of queries.

    Same contract as :func:`gather_l2`; the block comes from the shapes
    (``l2dist.block_queries``), and M and B are padded internally (pad
    slots and pad queries read row 0 and are dropped).
    """
    safe = jnp.maximum(ids, 0)
    if use_ref:
        d2 = ref.gather_l2_ref(base, safe, queries)
    else:
        interp = _on_cpu() if interpret is None else interpret
        d2 = gather_l2_tiled_pallas(_pad_lane(base, 1), safe,
                                    _pad_lane(queries, 1), interpret=interp)
    return jnp.where(ids >= 0, d2, jnp.inf)
