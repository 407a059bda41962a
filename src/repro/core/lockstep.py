"""The compacting driver of the beam engines' lock-step hop loops.

Both engines (``search._beam_search_batch``, ``probing._beam_probing_batch``)
advance a whole batch of queries one iteration at a time, and a finished row
is a masked no-op: it still pays every per-row op of an iteration (the
bitset's word gather and scatter, the distance kernel, the merge sort) until
the batch's slowest query stops.  ``run`` drives such a loop in stages of
shrinking static batch sizes instead of one ``while_loop`` over all B rows:

1. a stage loops while more of its rows are active than the next rung of the
   ladder holds;
2. the stage's rows are then written back to the full-size result at their
   original positions (``origin``), the active rows are gathered, in order,
   to the front, and the next rung's worth of rows continues in a loop of
   that static shape; a stage whose rows already fit the rung below it runs
   no iteration, so the loop drops as many rungs as the active count allows;
3. the last stage loops until no row is active and is written back.

A row left out of a stage has finished, so its state is final.  Rows are
independent — every per-row op of the loop body reads only its own row — so
a row's result is the same bit for bit whatever stage sizes it passed
through; only the slots the loop runs change.  The driver counts those in
the state's ``n_resident`` field: the iterations in which the row held a
slot, active or finished.

Every per-row leaf travels with the rows: the engine's state and the rows'
inputs (the queries, the RaBitQ query context).  A loop body gets the
inputs as an argument and closes over no per-row array.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

# The ladder of stage sizes below a batch: MIN_RUNG · LADDER_STEP**j.
# Measured on a TPU v5 lite at B = 1,024, four batches of the benchmark's
# SIFT-shaped queries (n = 32,768, l_max = 512): the exact engine took a mean
# 0.394 s a batch halving down to 16 rows, 0.413 s halving down to 32 and
# 0.431 s quartering (1,024, 256, 64, 16), against 1.632 s in one loop; the
# probing engine 1.195 s halving to 16 and 1.332 s quartering, against
# 3.391 s.  Halving compiles more loops (cold, the exact engine's program
# took 29.6 s against 20.3 s quartering and 11.4 s in one loop), which a
# warm compile cache does not pay again.  16 is the distance kernel's query
# block at [B, 64] (``l2dist.block_queries``): a smaller stage pads up to it.
LADDER_STEP = 2
MIN_RUNG = 16


def ladder(batch: int) -> tuple[int, ...]:
    """Static stage sizes for a batch of ``batch`` rows, largest first: the
    batch, then every ``MIN_RUNG · LADDER_STEP**j`` that is at most
    ``batch / LADDER_STEP``, so that each compaction at least divides the
    slots by the step.  A batch under ``LADDER_STEP · MIN_RUNG`` rows runs in
    one stage."""
    rungs = []
    r = MIN_RUNG
    while r * LADDER_STEP <= batch:
        rungs.append(r)
        r *= LADDER_STEP
    return (batch,) + tuple(reversed(rungs))


def _take(tree, idx):
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), tree)


def _put(full, idx, part):
    return jax.tree.map(
        lambda f, p: f.at[idx].set(p, unique_indices=True), full, part)


def run(active: Callable, body: Callable, state, inputs):
    """Loop ``state = body(state, inputs)`` until ``active(state)`` has no
    row set, compacting the batch down the ``ladder`` as rows finish.

    ``state`` is a NamedTuple of per-row leaves (leading axis B) with an
    int32[B] ``n_resident`` field; ``inputs`` is any pytree of per-row
    leaves that the body reads and never changes.  ``body`` must leave a row
    that ``active`` clears unchanged.  Returns the final state at full B,
    each row at its original position.
    """
    sizes = ladder(int(state.n_resident.shape[0]))

    def stage(s, x, floor):
        # The loop's own ops run under the engines' ``hop.transition``
        # scope, so that a profile charges every op of a loop to a phase.
        def cond(c):
            with jax.named_scope("hop.transition"):
                return jnp.sum(active(c[0]).astype(jnp.int32)) > floor

        def step(c):
            s, x = c
            s = body(s, x)
            with jax.named_scope("hop.transition"):
                s = s._replace(n_resident=s.n_resident + 1)
            return s, x

        return jax.lax.while_loop(cond, step, (s, x))[0]

    s, x, origin = state, inputs, None
    for i, size in enumerate(sizes):
        if i:
            with jax.named_scope("hop.compact"):
                # active rows first, each group in its original order
                keep = jnp.argsort(~active(s), stable=True)[:size]
                s, x = _take(s, keep), _take(x, keep)
                origin = keep if origin is None else origin[keep]
        floor = sizes[i + 1] if i + 1 < len(sizes) else 0
        s = stage(s, x, floor)
        if i:
            with jax.named_scope("hop.compact"):
                full = _put(full, origin, s)
        else:
            full = s
    return full
