"""Batched, fixed-shape beam search on proximity graphs.

Implements Algorithm 1 (greedy beam search) and Algorithm 3 (error-bounded
adaptive top-k search) of the paper as a *single* parameterized engine,
reformulated for lock-step execution on TPU.

``search`` is the **batch-level beam engine** — the only graph-search engine
in the repo.  One lock-step loop drives the whole query batch: each
iteration selects the ``beam_width`` (W) best unvisited in-window candidates
per query, gathers all ``B×W×M`` neighbor ids at once, dedups them against a
packed ``uint32`` visited bitset (O(1) test/set/clear — see ``bitset.py``),
and evaluates every fresh distance in a *single* fused gather+L2 call over
``[B, W·M]`` ids.  On TPU that call is the Pallas ``gather_l2_tiled`` kernel
— one big contraction per hop for the MXU instead of B tiny ones; on CPU it
lowers to the identical-math jnp path.  Queries that have exhausted their
window take the adaptive-α transition (grow ``l`` or stop) in the same
lock-step iteration; finished queries are masked no-ops, and the loop
compacts the batch to a smaller static shape as they finish
(``lockstep.run``), so the tail runs on the rows still active.

Semantics:

* The candidate set ``C`` is a fixed-width sorted array (ids, squared dists,
  visited flags) of capacity ``l_max + 1``.  Algorithm 3's literal "keep top
  l+1" prune is available as ``faithful_prune=True``: the merged candidate
  list is truncated to the top ``l+1`` every hop, and a pruned candidate
  that was never expanded has its visited bit *cleared* so it can re-enter
  (and be re-evaluated) once ``l`` grows — the re-insertion the literal
  algorithm relies on.  Read literally the prune can deadlock the adaptive
  loop: when ``l`` grows into a slot whose candidate was pruned away (or
  already visited), the stop test ``d(q,C[l]) ≥ α·d(q,C[k])`` sees ``+inf``
  and fires *regardless of α*, contradicting the paper's own Exp-6/7 (α must
  widen the search).  The default ``faithful_prune=False`` retains the full
  ``l_max+1`` buffer — the window ``l`` still gates which candidates may be
  *expanded* and the stop rule still reads ``C[l]``/``C[k]``, which realizes
  the intended adaptive behavior (and is how NSG-style pools with a growing
  capacity behave).
* The α-stop rule fires only when a query's window holds no unvisited
  candidate, so widening the per-hop frontier (W > 1) never skips the stop
  test — it only reorders the expansion schedule, which monotonic-graph
  convergence tolerates (the closure "expand until the window is exhausted"
  reaches the same fixed point family).

Correctness is checked against implementation-independent oracles, not a
reference engine: brute-force exact k-NN plus the paper's ``(1/δ)``
approximation bound (``repro.testing.oracle``, ``tests/test_conformance.py``),
and W=1 determinism / backend self-parity golden tests
(``tests/test_beam_engine.py``).

The distance evaluation is pluggable: ``backend`` selects
("auto" | "jnp" | "kernel" | "kernel_tiled"), and ``_beam_search_batch``
takes any ``batch_dist`` callable so the δ-EMQG searches (``probing.py``)
can swap in quantized implementations without touching the control flow.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import lockstep
from .bitset import (
    bitset_clear,
    bitset_make,
    bitset_set,
    bitset_test,
    unique_per_row,
)
from .types import (
    INVALID_ID,
    GraphIndex,
    SearchParams,
    SearchResult,
    take_rows,
)


def make_exact_dist_fn(vectors: jax.Array) -> Callable:
    """dist_fn(q, ids) → squared distances f32[M] (invalid ids → +inf)."""

    def dist_fn(q, ids):
        rows = take_rows(vectors, ids)
        diff = rows.astype(jnp.float32) - q.astype(jnp.float32)[None, :]
        d2 = jnp.sum(diff * diff, axis=-1)
        return jnp.where(ids >= 0, d2, jnp.inf)

    return dist_fn


def make_batch_dist_fn(vectors: jax.Array, backend: str = "auto") -> Callable:
    """batch_dist(queries f32[B, d], ids int32[B, K]) → d2 f32[B, K].

    Backends:
      * ``jnp``          — fused batch gather + reduce in plain XLA.
      * ``kernel``       — Pallas ``gather_l2`` (one row DMA per grid step).
      * ``kernel_tiled`` — Pallas ``gather_l2_tiled`` (multi-row DMA blocks).
      * ``auto``         — ``kernel_tiled`` on TPU, ``jnp`` elsewhere
                           (interpret-mode Pallas inside a hot loop would be
                           orders of magnitude slower than XLA on CPU).
    """
    if backend == "auto":
        backend = "kernel_tiled" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":

        def batch_dist(queries, ids):
            rows = take_rows(vectors, ids)                     # [B, K, d]
            diff = rows.astype(jnp.float32) - queries.astype(jnp.float32)[:, None, :]
            d2 = jnp.sum(diff * diff, axis=-1)
            return jnp.where(ids >= 0, d2, jnp.inf)

        return batch_dist
    if backend in ("kernel", "kernel_tiled"):
        from repro.kernels.l2dist import ops as l2ops  # lazy: optional dep

        fn = l2ops.gather_l2_tiled if backend == "kernel_tiled" else l2ops.gather_l2

        def batch_dist(queries, ids):
            return fn(vectors.astype(jnp.float32), ids,
                      queries.astype(jnp.float32))

        return batch_dist
    raise ValueError(f"unknown distance backend: {backend!r}")


def batch_merge_topc(ids_a, d2_a, vis_a, ids_b, d2_b, vis_b, cap: int):
    """Batched merge: [B, Ca] ⊎ [B, Cb] → top-``cap`` smallest d2 per row.

    The sort is stable (lower index wins ties), so appending the new
    entries after the existing buffer preserves the buffer's order for
    no-op merges — which is what keeps masked queries frozen in lock-step.
    """
    d2, ids, vis = sort_rows(jnp.concatenate([d2_a, d2_b], axis=1),
                             jnp.concatenate([ids_a, ids_b], axis=1),
                             jnp.concatenate([vis_a, vis_b], axis=1))
    return ids[:, :cap], d2[:, :cap], vis[:, :cap]


def sort_rows(d2, *payloads):
    """Stable ascending sort of each row of ``d2``, ``payloads`` carried
    along: the order of ``lax.top_k(-d2)`` without its per-row gathers
    (on a TPU v5 lite one ``take_along_axis`` over [512, 1001] took about
    6.5 times this sort with two payloads)."""
    return jax.lax.sort((d2,) + payloads, dimension=1, num_keys=1,
                        is_stable=True)


# ---------------------------------------------------------------------------
# Batch-level beam engine.
# ---------------------------------------------------------------------------


class _BeamState(NamedTuple):
    cand_ids: jax.Array    # int32[B, C]
    cand_d2: jax.Array     # f32[B, C]   squared dists, ascending (inf = empty)
    cand_vis: jax.Array    # bool[B, C]
    seen: jax.Array        # uint32[B, nw] packed visited bitset
    l: jax.Array           # int32[B]    current candidate window (Alg. 3)
    n_dist: jax.Array      # int32[B]    exact distance evaluations
    n_enc: jax.Array       # int32[B]    candidate encounters (pre-dedup)
    n_hops: jax.Array      # int32[B]    expansions
    n_iters: jax.Array     # int32[B]    loop iterations the row was active in
    n_resident: jax.Array  # int32[B]    loop iterations the row held a slot in
    done: jax.Array        # bool[B]
    saturated: jax.Array   # bool[B]     l hit l_max before the α-rule fired


def select_top_w(d2: jax.Array, mask: jax.Array, w: int):
    """Per-row W best (smallest d2) slots among ``mask``.

    Returns (sel int32[B, W], valid bool[B, W]); ``lax.top_k`` stability
    gives W=1 a deterministic lowest-index tie-break (same as ``argmin``).
    """
    masked = jnp.where(mask, d2, jnp.inf)
    neg, sel = jax.lax.top_k(-masked, w)
    return sel, jnp.isfinite(neg)


def resolve_beam_width(p: SearchParams, cap: int) -> int:
    """Validate and clamp ``p.beam_width`` against the buffer capacity."""
    if p.beam_width < 1:
        raise ValueError(
            f"beam_width must be ≥ 1, got {p.beam_width} (0 would never "
            "expand a frontier and the lock-step loop could not terminate)")
    return min(p.beam_width, cap)   # can't select more than the buffer holds


def adaptive_transition(p: SearchParams, cand_d2: jax.Array, l: jax.Array,
                        done: jax.Array, saturated: jax.Array,
                        conv: jax.Array):
    """Alg.-3 line 11 lock-step transition for window-exhausted queries.

    Shared by the graph and probing beam engines so the stop rule can never
    desynchronize between them.  ``conv`` masks the queries taking the
    transition this iteration; others pass through unchanged.
    Returns (l, done, saturated).
    """
    if not p.adaptive:
        return l, done | conv, saturated
    C = cand_d2.shape[1]
    alpha2 = jnp.float32(p.alpha * p.alpha)
    # stop iff d(q, C[l]) ≥ α · d(q, C[k])
    d2_l = jnp.take_along_axis(
        cand_d2, jnp.minimum(l - 1, C - 1)[:, None], axis=1)[:, 0]
    d2_k = cand_d2[:, p.k - 1]
    stop = d2_l >= alpha2 * d2_k
    at_cap = l >= p.l_max
    new_l = jnp.minimum(l + p.l_step, p.l_max)
    return (
        jnp.where(conv & ~stop, new_l, l),
        done | (conv & (stop | at_cap)),
        saturated | (conv & at_cap & ~stop),
    )


def faithful_prune_merge(cand_ids, cand_d2, cand_vis, new_ids, d2_new,
                         seen, l, cap: int):
    """Literal Alg.-3 line-9 merge: full sort of buffer ∪ fresh, keep the top
    ``l+1`` per row, and *clear the visited bits* of pruned candidates that
    were never expanded so they can re-enter once ``l`` grows (the
    re-insertion the literal prune relies on; expanded nodes keep their bits
    — they play the role of the paper's visited set T).

    Returns (cand_ids, cand_d2, cand_vis, seen), buffers trimmed to ``cap``
    columns (safe: ``l+1 ≤ l_max+1 = cap`` bounds the kept prefix).
    """
    ids_all = jnp.concatenate([cand_ids, new_ids], axis=1)
    d2_all = jnp.concatenate([cand_d2, d2_new], axis=1)
    vis_all = jnp.concatenate(
        [cand_vis, jnp.zeros_like(new_ids, jnp.bool_)], axis=1)
    d2_s, ids_s, vis_s = sort_rows(d2_all, ids_all, vis_all)   # full sort
    pos_all = jnp.arange(ids_s.shape[1], dtype=jnp.int32)[None, :]
    keep = pos_all <= l[:, None]
    # pruned ∧ unexpanded → clearable; ids are unique per row (buffer entries
    # are unique and fresh ids were, by definition, not in the buffer)
    clearable = jnp.where(keep | vis_s, INVALID_ID, ids_s)
    with jax.named_scope("hop.visited"):
        seen = bitset_clear(seen, clearable)
    return (jnp.where(keep, ids_s, INVALID_ID)[:, :cap],
            jnp.where(keep, d2_s, jnp.inf)[:, :cap],
            (keep & vis_s)[:, :cap],
            seen)


def _beam_search_batch(
    graph: GraphIndex,
    inputs,                    # per-row inputs of batch_dist: f32[B, d] queries
    start: jax.Array,          # int32[B]
    p: SearchParams,
    batch_dist: Callable,      # (inputs, ids [B, K]) → d2 [B, K]
    faithful_prune: bool = False,
) -> _BeamState:
    """The beam engine's hop loop (``search``, and AGS's traversal), driven
    by ``lockstep.run``: the batch compacts as its rows finish.  ``inputs``
    (the queries, or any pytree of per-row arrays such as RaBitQ query
    contexts) travel with the rows and reach ``batch_dist`` with them."""
    B = start.shape[0]
    C = p.l_max + 1
    W = resolve_beam_width(p, C)
    M = graph.neighbors.shape[1]
    n = graph.n

    pos = jnp.arange(C, dtype=jnp.int32)[None, :]      # [1, C]

    d2_start = batch_dist(inputs, start[:, None])[:, 0]
    st = _BeamState(
        cand_ids=jnp.full((B, C), INVALID_ID, jnp.int32).at[:, 0].set(start),
        cand_d2=jnp.full((B, C), jnp.inf, jnp.float32).at[:, 0].set(d2_start),
        cand_vis=jnp.zeros((B, C), jnp.bool_),
        seen=bitset_set(bitset_make(B, n), start[:, None]),
        l=jnp.full((B,), min(max(p.l0, p.k), p.l_max), jnp.int32),
        n_dist=jnp.ones((B,), jnp.int32),
        n_enc=jnp.ones((B,), jnp.int32),
        n_hops=jnp.zeros((B,), jnp.int32),
        n_iters=jnp.zeros((B,), jnp.int32),
        n_resident=jnp.zeros((B,), jnp.int32),
        done=jnp.zeros((B,), jnp.bool_),
        saturated=jnp.zeros((B,), jnp.bool_),
    )

    def active_mask(s: _BeamState):
        return (~s.done) & (s.n_hops < p.max_hops)

    # Each phase of an iteration runs under a ``hop.*`` named scope, which
    # only tags the ops' metadata: a profile of the compiled loop can then
    # charge every device op to its phase, whatever XLA names the fusion.
    # The body runs at each stage's batch size S.
    def body(s: _BeamState, inputs) -> _BeamState:
        S = s.l.shape[0]
        # -- frontier selection: W best unvisited in-window per query --------
        with jax.named_scope("hop.select"):
            rows = jnp.arange(S, dtype=jnp.int32)[:, None]
            active = active_mask(s)
            window = (pos < s.l[:, None]) & (s.cand_ids >= 0) & (~s.cand_vis)
            window &= active[:, None]
            has_frontier = jnp.any(window, axis=1)
            sel, selv = select_top_w(s.cand_d2, window, W)
            selv &= (active & has_frontier)[:, None]
            vis_sel = jnp.take_along_axis(s.cand_vis, sel, axis=1) | selv
            cand_vis = s.cand_vis.at[rows, sel].set(vis_sel)
            u_ids = jnp.where(
                selv, jnp.take_along_axis(s.cand_ids, sel, axis=1), INVALID_ID)
            n_hops = s.n_hops + jnp.sum(selv, axis=1).astype(jnp.int32)

        # -- neighbor gather ------------------------------------------------
        with jax.named_scope("hop.expand"):
            nbrs = jnp.take(graph.neighbors, jnp.maximum(u_ids, 0), axis=0)
            nbrs = jnp.where(selv[:, :, None], nbrs,
                             INVALID_ID).reshape(S, W * M)
            # encounters: every valid neighbor id this hop produced,
            # pre-dedup — the dedup-independent Exp-5 counter (the bitset
            # never re-evaluates pruned-then-reencountered nodes, so n_dist
            # undercounts)
            n_enc = s.n_enc + jnp.sum(nbrs >= 0, axis=1).astype(jnp.int32)

        # -- bitset dedup -----------------------------------------------------
        with jax.named_scope("hop.visited"):
            fresh = (nbrs >= 0) & ~bitset_test(s.seen, nbrs)
            new_ids = unique_per_row(nbrs, fresh)              # [B, W·M]
            seen = bitset_set(s.seen, new_ids)

        # -- the hot path: one fused gather+L2 over the whole batch ----------
        with jax.named_scope("hop.distance"):
            d2_new = batch_dist(inputs, new_ids)
            n_dist = s.n_dist + jnp.sum(new_ids >= 0, axis=1).astype(jnp.int32)

        with jax.named_scope("hop.merge"):
            if faithful_prune:
                cand_ids, cand_d2, cand_vis, seen = faithful_prune_merge(
                    s.cand_ids, s.cand_d2, cand_vis, new_ids, d2_new,
                    seen, s.l, C)
            else:
                cand_ids, cand_d2, cand_vis = batch_merge_topc(
                    s.cand_ids, s.cand_d2, cand_vis,
                    new_ids, d2_new, jnp.zeros_like(fresh), C)

        # -- adaptive transition for window-exhausted queries ----------------
        with jax.named_scope("hop.transition"):
            conv = active & ~has_frontier
            l, done, saturated = adaptive_transition(
                p, cand_d2, s.l, s.done, s.saturated, conv)
            # lock-step iterations this row was active in: the batch's
            # largest is the loop's trip count
            n_iters = s.n_iters + active.astype(jnp.int32)

        return s._replace(cand_ids=cand_ids, cand_d2=cand_d2,
                          cand_vis=cand_vis, seen=seen, l=l, n_dist=n_dist,
                          n_enc=n_enc, n_hops=n_hops, n_iters=n_iters,
                          done=done, saturated=saturated)

    return lockstep.run(active_mask, body, st, inputs)


@partial(jax.jit, static_argnames=("params", "faithful_prune",
                                   "with_candidates", "backend"))
def search(
    graph: GraphIndex,
    queries: jax.Array,                 # f32[B, d]
    params: SearchParams,
    start: Optional[jax.Array] = None,  # int32[B] or None → medoid
    faithful_prune: bool = False,
    with_candidates: bool = False,
    backend: str = "auto",
):
    """Batched Alg. 1 / Alg. 3 search on the lock-step beam engine.

    Returns SearchResult (and optionally the final candidate buffers for
    local-optimum analysis).  ``params.beam_width`` sets the per-hop frontier
    width W; W=1 is deterministic greedy best-first (golden-tested for
    run-to-run and cross-backend self-parity).

    ``faithful_prune=True`` runs the literal Alg.-3 top-(l+1) prune on the
    same engine: the candidate buffer is truncated to ``l+1`` every hop and
    pruned-but-never-expanded candidates have their visited bits cleared so
    they can re-enter (and be re-evaluated) when ``l`` grows — see
    ``faithful_prune_merge``.  It composes with any ``beam_width`` and
    ``backend``.
    """
    B = queries.shape[0]
    if start is None:
        start = jnp.broadcast_to(graph.medoid, (B,)).astype(jnp.int32)
    batch_dist = make_batch_dist_fn(graph.vectors, backend)
    st = _beam_search_batch(graph, queries, start, params, batch_dist,
                            faithful_prune=faithful_prune)
    k = params.k
    res = SearchResult(
        ids=st.cand_ids[:, :k],
        dists=jnp.sqrt(jnp.maximum(st.cand_d2[:, :k], 0.0)),
        n_dist_comps=st.n_dist,
        n_approx_comps=jnp.zeros_like(st.n_dist),
        n_hops=st.n_hops,
        final_l=st.l,
        saturated=st.saturated,
        n_encounters=st.n_enc,
        n_iters=st.n_iters,
        n_resident=st.n_resident,
    )
    if with_candidates:
        return res, st.cand_ids, jnp.sqrt(jnp.maximum(st.cand_d2, 0.0))
    return res


def greedy_search(graph: GraphIndex, queries: jax.Array, k: int, l: int,
                  start: Optional[jax.Array] = None, max_hops: int = 512,
                  beam_width: int = 1, backend: str = "auto") -> SearchResult:
    """Algorithm 1 with fixed candidate width l (the ablation δ-EMG-GS)."""
    p = SearchParams(k=k, l0=l, l_max=l, adaptive=False, max_hops=max_hops,
                     beam_width=beam_width)
    return search(graph, queries, p, start=start, backend=backend)


def error_bounded_search(graph: GraphIndex, queries: jax.Array, k: int,
                         alpha: float, l_max: int = 256, l_step: int = 1,
                         start: Optional[jax.Array] = None,
                         max_hops: int = 2048, beam_width: int = 1,
                         **kw) -> SearchResult:
    """Algorithm 3: adaptive candidate width with the α stop rule."""
    p = SearchParams(k=k, l0=k, l_max=l_max, l_step=l_step, alpha=alpha,
                     adaptive=True, max_hops=max_hops, beam_width=beam_width)
    return search(graph, queries, p, start=start, **kw)


# ---------------------------------------------------------------------------
# Theorem-4 instrumentation (Exp-6 / Exp-7).
# ---------------------------------------------------------------------------

@jax.jit
def local_optimum_mask(graph: GraphIndex, queries: jax.Array, cand_ids: jax.Array):
    """bool[B, C]: candidate c is a local optimum w.r.t. its query
    (no out-neighbor of c is strictly closer to q than c)."""

    def one(q, ids):
        d2_c = jnp.where(
            ids >= 0,
            jnp.sum((take_rows(graph.vectors, ids) - q[None, :]) ** 2, axis=-1),
            jnp.inf,
        )

        def check(cid, d2c):
            nbrs = jnp.take(graph.neighbors, jnp.maximum(cid, 0), axis=0)
            rows = take_rows(graph.vectors, nbrs)
            d2n = jnp.sum((rows - q[None, :]) ** 2, axis=-1)
            d2n = jnp.where(nbrs >= 0, d2n, jnp.inf)
            return (cid >= 0) & jnp.all(d2n >= d2c)

        return jax.vmap(check)(ids, d2_c)

    return jax.vmap(one)(queries, cand_ids)


def theorem4_delta_prime(graph: GraphIndex, queries: jax.Array, cand_ids: jax.Array,
                         cand_dists: jax.Array, k: int, delta: float):
    """Per-query (found: bool, δ′: f32) per Theorem 4.

    δ′ = δ · d(q, u) / d(q, r_(k)) with u the *farthest* local-optimum node in
    the final candidate set outside the returned top-k (wider search ⇒ larger
    d(q,u) ⇒ tighter bound — Exp-7's observation).
    """
    is_opt = local_optimum_mask(graph, queries, cand_ids)
    pos = jnp.arange(cand_ids.shape[1])[None, :]
    outside = pos >= k
    eligible = is_opt & outside & (cand_ids >= 0) & jnp.isfinite(cand_dists)
    d_u = jnp.max(jnp.where(eligible, cand_dists, -jnp.inf), axis=1)
    found = jnp.any(eligible, axis=1)
    d_rk = cand_dists[:, k - 1]
    delta_prime = jnp.where(found, delta * d_u / jnp.maximum(d_rk, 1e-30), 0.0)
    return found, delta_prime
