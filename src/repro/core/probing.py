"""Algorithm 5 — Probing top-k ANN search on δ-EMQG.

Two-tier traversal: *expansion* walks the graph using RaBitQ approximate
distances (cheap, batched over a node's whole neighbor list); *probing*
promotes the best approximate candidate to the exact tier only when the
exact frontier has stopped improving.  The adaptive outer-``l`` loop and the
α stop rule are inherited from Algorithm 3 and apply to the exact tier.

``probing_search`` is the batch-level beam engine — the only Algorithm-5
engine in the repo.  One lock-step loop drives the whole batch (compacted
as rows finish, ``lockstep.run``); per iteration
each query either *probes* its ``beam_width`` best unprobed approximate
candidates (their exact distances are evaluated in one fused gather+L2 call
over ``[B, W]`` ids) or *expands* its W best unvisited exact candidates
(``B×W×M`` neighbor ids deduped against a packed visited bitset, approximate
distances in one batched RaBitQ estimate).  The NeedProbing rule
(lines 22-28) decides per query; finished queries are masked no-ops.

Fixed-shape state: one candidate list of capacity ``l_max+1`` holds both
tiers, C_e (exact d², expanded or not) and C_a (RaBitQ estimates, not yet
probed), each entry ranked by the distance it has.  The window of Alg. 3 is
the list's first ``l`` entries: a probe takes its candidate out of the list
and merges it back at its exact distance, so a probed candidate never holds
a window slot by its estimate.  The loop ends for a query when its window
holds only expanded exact candidates, and the answer is the exact tier's
first ``k``.

Also provides AGS (approximate greedy search + exact rerank — SymphonyQG's
search, the paper's δ-EMQG-AGS ablation), built on the same batch engine:
the generic ``_beam_search_batch`` traversal runs with a RaBitQ approximate
``batch_dist``, then one fused exact gather+L2 call reranks the final
candidate buffers.

Correctness is checked against implementation-independent oracles — brute
force exact k-NN plus the paper's ``(1/δ)`` bound (``repro.testing.oracle``,
``tests/test_conformance.py``) — not a reference engine.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import lockstep, rabitq
from .bitset import bitset_make, bitset_set, bitset_test, unique_per_row
from .search import (
    _beam_search_batch,
    adaptive_transition,
    make_batch_dist_fn,
    resolve_beam_width,
    select_top_w,
    sort_rows,
)
from .types import INVALID_ID, EMQGIndex, SearchParams, SearchResult


# ---------------------------------------------------------------------------
# Batch-level beam engine.
# ---------------------------------------------------------------------------


# Tiers of a candidate-list entry.
_APPROX = 0     # RaBitQ estimate only: a probe may promote it
_EXACT = 1      # exact distance, not yet expanded
_EXPANDED = 2   # exact distance, neighbours walked


class _BeamPState(NamedTuple):
    ids: jax.Array         # int32[B, C]  candidates of both tiers, by d2
    d2: jax.Array          # f32[B, C]    exact d² or RaBitQ estimate
    tier: jax.Array        # int8[B, C]   _APPROX | _EXACT | _EXPANDED
    seen: jax.Array        # uint32[B, nw] every id that entered the list
    d2_last: jax.Array     # f32[B]  exact d² of the last expanded node
    l: jax.Array           # int32[B]
    n_dist: jax.Array      # int32[B]
    n_approx: jax.Array    # int32[B]
    n_enc: jax.Array       # int32[B]  candidate encounters (pre-dedup)
    n_hops: jax.Array      # int32[B]
    n_iters: jax.Array     # int32[B]  loop iterations the row was active in
    n_resident: jax.Array  # int32[B]  loop iterations the row held a slot in
    done: jax.Array        # bool[B]
    saturated: jax.Array   # bool[B]


def _beam_probing_batch(
    neighbors: jax.Array,      # int32[n, M]
    n_nodes: int,
    batch_exact: Callable,     # (queries [B,d], ids [B,K]) → d2 [B,K]
    batch_approx: Callable,    # (ctx [B], ids [B,K]) → d2 [B,K]
    queries: jax.Array,
    ctx,                       # rabitq.QueryCtx of each row
    start: jax.Array,
    p: SearchParams,
) -> _BeamPState:
    """Algorithm 5's hop loop, driven by ``lockstep.run``: the batch
    compacts as its rows finish, the queries and their RaBitQ contexts
    travelling with the rows."""
    B = queries.shape[0]
    C = p.l_max + 1
    W = resolve_beam_width(p, C)
    M = neighbors.shape[1]

    pos = jnp.arange(C, dtype=jnp.int32)[None, :]

    d2_s = batch_exact(queries, start[:, None])[:, 0]
    st = _BeamPState(
        ids=jnp.full((B, C), INVALID_ID, jnp.int32).at[:, 0].set(start),
        d2=jnp.full((B, C), jnp.inf, jnp.float32).at[:, 0].set(d2_s),
        tier=jnp.full((B, C), _APPROX, jnp.int8).at[:, 0].set(_EXACT),
        seen=bitset_set(bitset_make(B, n_nodes), start[:, None]),
        d2_last=d2_s,
        l=jnp.full((B,), min(max(p.l0, p.k), p.l_max), jnp.int32),
        n_dist=jnp.ones((B,), jnp.int32),
        n_approx=jnp.zeros((B,), jnp.int32),
        n_enc=jnp.ones((B,), jnp.int32),
        n_hops=jnp.zeros((B,), jnp.int32),
        n_iters=jnp.zeros((B,), jnp.int32),
        n_resident=jnp.zeros((B,), jnp.int32),
        done=jnp.zeros((B,), jnp.bool_),
        saturated=jnp.zeros((B,), jnp.bool_),
    )

    def active_mask(s: _BeamPState):
        return (~s.done) & (s.n_hops < p.max_hops)

    # The phases run under the exact engine's ``hop.*`` named scopes
    # (``search._beam_search_batch``), with the RaBitQ estimates under
    # ``hop.estimate``; a scope only tags the ops' metadata.  The body runs
    # at each stage's batch size S.
    def body(s: _BeamPState, inputs) -> _BeamPState:
        queries, ctx = inputs
        S = s.l.shape[0]
        with jax.named_scope("hop.select"):
            rows = jnp.arange(S, dtype=jnp.int32)[:, None]
            # The window is the first l entries of one list that ranks
            # exact candidates by their distance and unprobed ones by their
            # estimate; a probed entry leaves its estimate's rank and
            # re-enters at its exact distance's.
            active = active_mask(s)
            win = (pos < s.l[:, None]) & (s.ids >= 0) & active[:, None]
            win_e = win & (s.tier == _EXACT)
            win_a = win & (s.tier == _APPROX)
            has_u = jnp.any(win_e, axis=1)
            has_w = jnp.any(win_a, axis=1)
            d2_u = jnp.min(jnp.where(win_e, s.d2, jnp.inf), axis=1)
            d2_w = jnp.min(jnp.where(win_a, s.d2, jnp.inf), axis=1)

            # NeedProbing (lines 22-28): probe when the exact frontier
            # stopped improving and the approx tier has something closer.
            need_probe = jnp.where(
                ~has_u,
                has_w,
                (d2_u > s.d2_last) & has_w & (d2_w < d2_u),
            )
            probing = active & need_probe
            expanding = active & ~need_probe & has_u
            conv = active & ~has_u & ~has_w

            # -- probe branch: the W best unprobed candidates, taken out of
            #    the list; the merge puts them back at their exact distance
            sel_w, selv_w = select_top_w(s.d2, win_a, W)
            selv_w &= probing[:, None]
            picked = jnp.take_along_axis(s.ids, sel_w, axis=1)
            w_ids = jnp.where(selv_w, picked, INVALID_ID)
            ids = s.ids.at[rows, sel_w].set(
                jnp.where(selv_w, INVALID_ID, picked))
            d2 = s.d2.at[rows, sel_w].set(jnp.where(
                selv_w, jnp.inf, jnp.take_along_axis(s.d2, sel_w, axis=1)))

            # -- expand branch: the W best unexpanded exact candidates -------
            sel_u, selv_u = select_top_w(s.d2, win_e, W)
            selv_u &= expanding[:, None]
            tier_sel = jnp.where(
                selv_u, jnp.int8(_EXPANDED),
                jnp.take_along_axis(s.tier, sel_u, axis=1))
            tier = s.tier.at[rows, sel_u].set(tier_sel)
            u_ids = jnp.where(
                selv_u, jnp.take_along_axis(s.ids, sel_u, axis=1),
                INVALID_ID)
            d2_u_sel = jnp.where(
                selv_u, jnp.take_along_axis(s.d2, sel_u, axis=1), -jnp.inf)
            # "last expanded" = the worst of this hop's frontier (W=1: u).
            d2_last = jnp.where(expanding, jnp.max(d2_u_sel, axis=1),
                                s.d2_last)
            n_hops = s.n_hops + jnp.sum(selv_w, axis=1).astype(jnp.int32) \
                + jnp.sum(selv_u, axis=1).astype(jnp.int32)

        with jax.named_scope("hop.distance"):                # probe: exact
            d2_probe = batch_exact(queries, w_ids)             # [B, W] fused
            n_dist = s.n_dist + jnp.sum(w_ids >= 0, axis=1).astype(jnp.int32)

        with jax.named_scope("hop.expand"):
            nbrs = jnp.take(neighbors, jnp.maximum(u_ids, 0), axis=0)
            nbrs = jnp.where(selv_u[:, :, None], nbrs,
                             INVALID_ID).reshape(S, W * M)
            # encounters: valid neighbor ids pre-dedup, plus probed ones
            n_enc = s.n_enc + jnp.sum(nbrs >= 0, axis=1).astype(jnp.int32) \
                + jnp.sum(w_ids >= 0, axis=1).astype(jnp.int32)

        with jax.named_scope("hop.visited"):
            fresh = (nbrs >= 0) & ~bitset_test(s.seen, nbrs)
            new_ids = unique_per_row(nbrs, fresh)
            seen = bitset_set(s.seen, new_ids)

        with jax.named_scope("hop.estimate"):                # expand: approx
            d2a = batch_approx(ctx, new_ids)                   # [B, W·M]
            n_approx = s.n_approx \
                + jnp.sum(new_ids >= 0, axis=1).astype(jnp.int32)

        # -- one merge: the list, the probed ids at their exact distances
        #    (per query only one of the two new blocks holds real entries)
        with jax.named_scope("hop.merge"):
            d2, ids, tier = sort_rows(
                jnp.concatenate([d2, d2_probe, d2a], axis=1),
                jnp.concatenate([ids, w_ids, new_ids], axis=1),
                jnp.concatenate([tier, jnp.full_like(w_ids, _EXACT, jnp.int8),
                                 jnp.full_like(new_ids, _APPROX, jnp.int8)],
                                axis=1))
            ids, d2, tier = ids[:, :C], d2[:, :C], tier[:, :C]

        # -- adaptive transition for exhausted queries: every valid entry of
        #    their window is expanded, so C[l] and C[k] are exact ----------
        with jax.named_scope("hop.transition"):
            l, done, saturated = adaptive_transition(
                p, d2, s.l, s.done, s.saturated, conv)
            n_iters = s.n_iters + active.astype(jnp.int32)

        return s._replace(
            ids=ids, d2=d2, tier=tier, seen=seen, d2_last=d2_last, l=l,
            n_dist=n_dist, n_approx=n_approx, n_enc=n_enc, n_hops=n_hops,
            n_iters=n_iters, done=done, saturated=saturated)

    return lockstep.run(active_mask, body, st, (queries, ctx))


@partial(jax.jit, static_argnames=("params", "use_kernel", "with_candidates",
                                   "backend"))
def probing_search(
    index: EMQGIndex,
    queries: jax.Array,
    params: SearchParams,
    start: Optional[jax.Array] = None,
    use_kernel: bool = False,
    with_candidates: bool = False,
    backend: str = "auto",
):
    """Batched Algorithm 5 on the lock-step beam engine.  ``use_kernel``
    routes the S₊ contraction through the Pallas bitdot kernel
    (interpret-mode on CPU); ``backend`` selects the exact-tier gather+L2
    implementation (see ``make_batch_dist_fn``)."""
    B = queries.shape[0]
    g, codes = index.graph, index.codes
    if start is None:
        start = jnp.broadcast_to(g.medoid, (B,)).astype(jnp.int32)
    batch_exact = make_batch_dist_fn(g.vectors, backend)
    bitdot_fn = None
    if use_kernel:
        from repro.kernels.bitdot.ops import bitdot as bitdot_fn  # lazy: optional dep

    ctx = jax.vmap(lambda q: rabitq.prepare_query(codes, q))(queries)

    def batch_approx(ctx, ids):
        return jax.vmap(
            lambda c, i: rabitq.estimate_sqdist(codes, c, i, bitdot_fn=bitdot_fn)
        )(ctx, ids)

    st = _beam_probing_batch(g.neighbors, g.n, batch_exact, batch_approx,
                             queries, ctx, start, params)
    # the exact tier, in order: an answer never carries an estimate (a row
    # cut off by ``max_hops`` may hold unprobed entries ahead of it)
    exact = st.tier != _APPROX
    ce_d2, ce_ids = sort_rows(jnp.where(exact, st.d2, jnp.inf),
                              jnp.where(exact, st.ids, INVALID_ID))
    k = params.k
    res = SearchResult(
        ids=ce_ids[:, :k],
        dists=jnp.sqrt(jnp.maximum(ce_d2[:, :k], 0.0)),
        n_dist_comps=st.n_dist,
        n_approx_comps=st.n_approx,
        n_hops=st.n_hops,
        final_l=st.l,
        saturated=st.saturated,
        n_encounters=st.n_enc,
        n_iters=st.n_iters,
        n_resident=st.n_resident,
        # every exact evaluation but the start's is a probe
        n_probes=st.n_dist - 1,
    )
    if with_candidates:
        return res, ce_ids, jnp.sqrt(jnp.maximum(ce_d2, 0.0))
    return res


def error_bounded_probing_search(index: EMQGIndex, queries: jax.Array, k: int,
                                 alpha: float, l_max: int = 256,
                                 l_step: int = 1, max_hops: int = 4096,
                                 beam_width: int = 1, **kw) -> SearchResult:
    p = SearchParams(k=k, l0=k, l_max=l_max, l_step=l_step, alpha=alpha,
                     adaptive=True, max_hops=max_hops, beam_width=beam_width)
    return probing_search(index, queries, p, **kw)


# ---------------------------------------------------------------------------
# AGS — approximate greedy search (SymphonyQG), the δ-EMQG-AGS ablation:
# plain Algorithm-1 traversal guided purely by approximate distances, then a
# single exact rerank of the final candidate list.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("params", "backend"))
def ags_search(index: EMQGIndex, queries: jax.Array, params: SearchParams,
               start: Optional[jax.Array] = None,
               backend: str = "auto") -> SearchResult:
    """Batched AGS on the lock-step beam engine.

    The generic ``_beam_search_batch`` traversal only consumes the graph
    topology and a ``batch_dist`` callable, so swapping in the RaBitQ
    estimator yields the approximate-guided frontier for free — the whole
    batch walks in one lock-step loop with the same bitset dedup and
    masked adaptive transitions as the exact engine.  The final candidate
    buffers (up to ``l_max+1`` ids per query) are then reranked with one
    fused exact gather+L2 call (``backend`` selects its implementation).

    Counters: ``n_approx_comps`` is the traversal's estimator evaluations;
    ``n_dist_comps`` is the exact rerank cost (valid buffer entries).
    """
    B = queries.shape[0]
    g, codes = index.graph, index.codes
    if start is None:
        start = jnp.broadcast_to(g.medoid, (B,)).astype(jnp.int32)

    ctx = jax.vmap(lambda q: rabitq.prepare_query(codes, q))(queries)

    def batch_approx(ctx, ids):
        return jax.vmap(
            lambda c, i: rabitq.estimate_sqdist(codes, c, i))(ctx, ids)

    # the traversal's rows carry their RaBitQ contexts, not the queries
    st = _beam_search_batch(g, ctx, start, params, batch_approx)

    # exact rerank of the whole final buffer, one fused call
    batch_exact = make_batch_dist_fn(g.vectors, backend)
    d2 = batch_exact(queries, st.cand_ids)
    neg, order = jax.lax.top_k(-d2, d2.shape[1])
    ids = jnp.take_along_axis(st.cand_ids, order, axis=1)
    d2 = -neg
    k = params.k
    return SearchResult(
        ids=ids[:, :k],
        dists=jnp.sqrt(jnp.maximum(d2[:, :k], 0.0)),
        n_dist_comps=jnp.sum(st.cand_ids >= 0, axis=1).astype(jnp.int32),
        n_approx_comps=st.n_dist,
        n_hops=st.n_hops,
        final_l=st.l,
        saturated=st.saturated,
        n_encounters=st.n_enc,
        n_iters=st.n_iters,
        n_resident=st.n_resident,
    )
