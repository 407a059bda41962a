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

``gather_l2_tiled`` — the beam-engine hot path.  The base matrix stays in
                  HBM (``memory_space=ANY``); one grid step covers a block
                  of Q queries × all K slots (K padded to a multiple of 8,
                  B to a multiple of Q; pad slots and queries read row 0
                  and are dropped).  Q comes from the shape
                  (``block_queries``): the largest multiple of 16 whose
                  (Q, K, d) f32 row tile fits 512 KiB — Q=16 at K=64,
                  d=128, so [1024, 64] ids take 64 steps.  The row copies
                  overlap across steps: two VMEM row tiles and two DMA
                  semaphores, and step i launches step i+1's Q·K row
                  copies before it waits on its own, then reduces its
                  (Q, K, d) tile on the VPU (f32 subtract, square, sum
                  over d) into one (Q, K) output block.  The ids stay in
                  HBM too and reach SMEM a step's block at a time, double
                  buffered, two steps ahead of the rows.  VMEM: 2 × the
                  row tile (1 MiB at K=64, d=128) plus the pipelined query
                  and output blocks; SMEM: 2 × Q·K int32 (8 KiB).
                  Operands stay (ids, base, queries), the queries' first
                  dim B, so a call's work reads from its HLO shapes.

``gather_l2_tiled`` and the bitdot kernels compile for a TPU v5e
(``tests/test_tpu_compile.py``), ``gather_l2_tiled`` at d ≤ 128 only: past
one lane tile a base row is not contiguous in the (8, 128)-tiled HBM layout,
and Mosaic refuses its one-row copy.  ``batched_l2`` and ``gather_l2`` do not:
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
# one grid step per block of Q queries × all K slots.
# ---------------------------------------------------------------------------

_SUBLANE = 8
_QUERY_STEP = 16                  # Q·K ids (K a multiple of 8): whole lanes
_ROW_TILE_BYTES = 512 * 1024      # one step's gathered rows, per buffer


def block_queries(B: int, K: int, d: int) -> int:
    """Queries per grid step for ids [B, K] into rows of d f32 (K a multiple
    of 8): the largest multiple of 16 whose (Q, K, d) row tile fits
    ``_ROW_TILE_BYTES``, at least 16, and no more than B rounded up to 16.
    A multiple of 16 makes a step's Q·K ids whole 128-lane rows, as their
    copy into SMEM requires."""
    fit = _ROW_TILE_BYTES // (K * d * 4) // _QUERY_STEP * _QUERY_STEP
    return min(max(fit, _QUERY_STEP), pl.cdiv(B, _QUERY_STEP) * _QUERY_STEP)


def _gather_l2_tiled_kernel(ids_hbm, base_hbm, q_ref, out_ref, ids_smem,
                            rows_vmem, ids_sem, rows_sem, *, n_queries: int,
                            n_slots: int, n_steps: int):
    Q, K = n_queries, n_slots
    i = pl.program_id(0)
    slot = i % 2

    def ids_copy(step, s):
        return pltpu.make_async_copy(ids_hbm.at[step], ids_smem.at[s],
                                     ids_sem.at[s])

    def row_copy(s, j, r, row):
        return pltpu.make_async_copy(base_hbm.at[pl.ds(row, 1), :],
                                     rows_vmem.at[s, j, pl.ds(r, 1), :],
                                     rows_sem.at[s])

    def start_rows(s):
        """Launch the Q·K row copies of the step whose ids sit in slot s."""
        def per_query(j, carry):
            for r in range(K):
                row_copy(s, j, r, ids_smem[s, 0, j * K + r]).start()
            return carry
        jax.lax.fori_loop(0, Q, per_query, 0)

    # Ids run two steps ahead of the rows, the rows one step ahead of the
    # arithmetic: step i launches step i+1's row copies (its ids arrived
    # during step i-1) and step i+2's ids before it waits on its own rows.
    @pl.when(i == 0)
    def _():
        first = ids_copy(0, 0)
        first.start()
        first.wait()
        start_rows(0)
        if n_steps > 1:
            ids_copy(1, 1).start()

    @pl.when(i + 1 < n_steps)
    def _():
        ids_copy(i + 1, 1 - slot).wait()
        start_rows(1 - slot)

        @pl.when(i + 2 < n_steps)
        def _():
            ids_copy(i + 2, slot).start()

    # A wait reads only the copy's size and semaphore: row 0 stands in.
    def wait_query(j, carry):
        for r in range(K):
            row_copy(slot, j, r, 0).wait()
        return carry
    jax.lax.fori_loop(0, Q, wait_query, 0)

    diff = rows_vmem[slot] - q_ref[...][:, None, :]
    out_ref[...] = jnp.sum(diff * diff, axis=2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_l2_tiled_pallas(base: jax.Array, ids: jax.Array, queries: jax.Array,
                           interpret: bool = False) -> jax.Array:
    B, K = ids.shape
    d = base.shape[1]
    Kp = pl.cdiv(K, _SUBLANE) * _SUBLANE
    Q = block_queries(B, Kp, d)
    Bp = pl.cdiv(B, Q) * Q
    steps = Bp // Q
    # Pad slots and queries read row 0; their outputs are dropped below.
    ids = jnp.pad(ids.astype(jnp.int32), ((0, Bp - B), (0, Kp - K)))
    queries = jnp.pad(queries.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_gather_l2_tiled_kernel, n_queries=Q, n_slots=Kp,
                          n_steps=steps),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # ids, copied per step
            pl.BlockSpec(memory_space=pl.ANY),      # base stays in HBM
            pl.BlockSpec((Q, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((Q, Kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
        scratch_shapes=[
            pltpu.SMEM((2, 1, Q * Kp), jnp.int32),
            pltpu.VMEM((2, Q, Kp, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids.reshape(steps, 1, Q * Kp), base.astype(jnp.float32), queries)
    return out[:B, :K]
