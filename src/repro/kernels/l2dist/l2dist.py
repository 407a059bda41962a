"""Pallas TPU kernels for the ANN distance hot path.

Two kernels, matching the two halves of a graph-search expansion:

``batched_l2``  — contraction:  rows f32[B, M, d] × queries f32[B, d]
                  → squared distances f32[B, M].
                  One grid step per query; the (M, d) neighbor tile and the
                  (1, d) query line live in VMEM; the cross term r·q is an
                  (M, d) × (d,) MXU contraction (dims padded to lane width
                  by the wrapper), the norm terms are VPU reductions.
                  VMEM per step ≈ M·d·4B (64×128 → 32 KiB) ≪ 16 MiB.

``gather_l2``   — fused gather + distance via scalar-prefetch indexing:
                  the neighbor-id array is prefetched into SMEM, and the
                  BlockSpec index_map picks base row ``ids[b, m]`` for grid
                  step (b, m) — HBM→VMEM DMA of exactly the needed row,
                  Pallas double-buffers successive rows.  This is the
                  TPU-native replacement for the CPU's pointer-chasing
                  per-neighbor loads; the wrapper clamps INVALID ids to row
                  0 and masks the output.

``gather_l2_tiled`` — the beam-engine hot path.  The single-row variant
                  issues one latency-bound DMA per grid step ((1, d) blocks);
                  the tiled variant keeps the base matrix in HBM
                  (``memory_space=ANY``), and each grid step launches
                  ``block_rows`` row DMAs back-to-back into a VMEM scratch
                  tile before a single vectorized (R, d) distance reduction —
                  R in-flight copies amortize DMA issue latency and the
                  compute runs on a full tile instead of one row.  VMEM per
                  step is R·d·4 B (8×128 → 4 KiB) plus the (1, d) query line;
                  the step's R row ids are blocked into SMEM.

``gather_l2_tiled`` and the bitdot kernels compile for a TPU v5e
(``tests/test_tpu_compile.py``).  ``batched_l2`` and ``gather_l2`` do not:
their (1, d) row blocks break the TPU tiling rule (the last two block dims
must divide (8, 128) or equal the array's), and nothing on the serve path
calls them.  All four are validated on CPU in interpret mode against
``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# batched_l2: rows [B, M, d] × queries [B, d] → d2 [B, M]
# ---------------------------------------------------------------------------

def _batched_l2_kernel(q_ref, rows_ref, out_ref):
    rows = rows_ref[0]                       # (M, d) VMEM tile
    q = q_ref[0]                             # (d,)
    rq = jnp.dot(rows, q, preferred_element_type=jnp.float32)   # MXU
    r2 = jnp.sum(rows * rows, axis=-1)                          # VPU
    q2 = jnp.sum(q * q)
    out_ref[0, :] = jnp.maximum(r2 + q2 - 2.0 * rq, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_l2_pallas(rows: jax.Array, queries: jax.Array,
                      interpret: bool = False) -> jax.Array:
    B, M, d = rows.shape
    return pl.pallas_call(
        _batched_l2_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, d), lambda b: (b, 0)),
            pl.BlockSpec((1, M, d), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, M), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.float32),
        interpret=interpret,
    )(queries.astype(jnp.float32), rows.astype(jnp.float32))


# ---------------------------------------------------------------------------
# gather_l2: base [n, d] + ids [B, M] + queries [B, d] → d2 [B, M]
# ---------------------------------------------------------------------------

def _gather_l2_kernel(ids_ref, base_row_ref, q_ref, out_ref):
    del ids_ref  # consumed by the index_map; kernel body only sees the row
    diff = base_row_ref[0] - q_ref[0]
    out_ref[0, 0] = jnp.sum(diff * diff)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_l2_pallas(base: jax.Array, ids: jax.Array, queries: jax.Array,
                     interpret: bool = False) -> jax.Array:
    B, M = ids.shape
    n, d = base.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, d), lambda b, m, ids: (ids[b, m], 0)),
            pl.BlockSpec((1, d), lambda b, m, ids: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda b, m, ids: (b, m)),
    )
    return pl.pallas_call(
        _gather_l2_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.float32),
        interpret=interpret,
    )(ids.astype(jnp.int32), base.astype(jnp.float32),
      queries.astype(jnp.float32))


# ---------------------------------------------------------------------------
# gather_l2_tiled: base [n, d] + ids [B, K] + queries [B, d] → d2 [B, K],
# R = block_rows gathered rows per grid step.
# ---------------------------------------------------------------------------

def _gather_l2_tiled_kernel(ids_ref, base_hbm, q_ref, out_ref, rows_vmem,
                            sem, *, block_rows: int):
    R = block_rows

    def row_dma(r):
        return pltpu.make_async_copy(
            base_hbm.at[pl.ds(ids_ref[0, 0, 0, r], 1), :],
            rows_vmem.at[pl.ds(r, 1), :],
            sem,
        )

    # Launch all R row copies on one semaphore, then drain: R equal-sized
    # DMAs in flight per grid step.
    for r in range(R):
        row_dma(r).start()
    for r in range(R):
        row_dma(r).wait()

    diff = rows_vmem[...] - q_ref[0]
    out_ref[0, 0, 0, :] = jnp.sum(diff * diff, axis=1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gather_l2_tiled_pallas(base: jax.Array, ids: jax.Array, queries: jax.Array,
                           block_rows: int = 8,
                           interpret: bool = False) -> jax.Array:
    B, K = ids.shape
    n, d = base.shape
    if K % block_rows:
        raise ValueError(f"K={K} must be a multiple of block_rows={block_rows}"
                         " (wrapper pads)")
    T = K // block_rows
    # Every block's last two dims equal its array's (the TPU tiling rule):
    # ids and the output get a unit axis before their R-wide row, the query
    # one before its d-wide row.  ids are blocked per grid step into SMEM
    # rather than scalar-prefetched whole: ids[B, K] at B=4096 outgrows the
    # 1 MiB SMEM.
    out = pl.pallas_call(
        functools.partial(_gather_l2_tiled_kernel, block_rows=block_rows),
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_rows), lambda b, t: (b, t, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),              # base stays in HBM
            pl.BlockSpec((1, 1, d), lambda b, t: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_rows),
                               lambda b, t: (b, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, 1, block_rows), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(ids.astype(jnp.int32).reshape(B, T, 1, block_rows),
      base.astype(jnp.float32), queries.astype(jnp.float32)[:, None, :])
    return out.reshape(B, K)
