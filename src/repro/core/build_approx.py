"""Algorithm 4 — near-linear approximate δ-EMG construction.

Host-orchestrated, accelerator-bulk design (the same split DiskANN/Vamana
builders use): the O(n·L) beam searches and the O(n·L·M·d) occlusion pruning
run as vmapped JAX computations over node blocks; the cheap, irregular graph
surgery (reverse edges, connectivity repair) runs in NumPy between
iterations.  Each refinement iteration is idempotent given its input graph,
which is what makes the per-iteration checkpointing fault-tolerant: a
restarted worker redoes at most one iteration.

Faithful to the paper:
  * bootstrap = top-M approximate kNN graph           (line 2)
  * per-node candidates from greedy search            (line 6)
  * LocallySelectNeighbors with δ_t(u,v) = 1 − d(u,v)/d(u,v_(t))   (line 21)
  * degree cap M, reverse edges, connectivity repair  (lines 8–15)
  * optional degree alignment for δ-EMQG (Sec. 6.1): short rows are filled
    up to M; with a fixed δ they keep every edge they had
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Optional


def _build_event(metrics, verbose: bool, phase: str, **fields) -> None:
    """Structured build progress: with a ``metrics`` registry the event is
    recorded (``build_progress`` ring entry + ``build_phase_seconds{phase}``
    histogram + ``build_nodes_total`` counter); ``verbose`` keeps the
    human-readable stderr-style line for CLI use.  Numbers come from the
    monotonic clock (``perf_counter``)."""
    if metrics is not None:
        metrics.event("build_progress", phase=phase, **fields)
        if "elapsed_s" in fields:
            metrics.histogram("build_phase_seconds",
                              {"phase": phase}).observe(fields["elapsed_s"])
        if "nodes" in fields:
            metrics.counter("build_nodes_total").inc(fields["nodes"])
    if verbose:
        body = " ".join(
            f"{k}={v:.1f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in fields.items())
        print(f"[build_approx] {phase}: {body}")

import jax
import jax.numpy as jnp
import numpy as np

from .distances import brute_force_knn, medoid as find_medoid, pairwise_sqdist
from .geometry import adaptive_deltas, select_neighbors
from .search import SearchParams, search
from .types import GraphIndex


@dataclasses.dataclass(frozen=True)
class BuildParams:
    max_degree: int = 32          # M
    beam_width: int = 64          # L (candidate set size, paper uses 1000 at 1M scale)
    t: int = 16                   # neighborhood-scale parameter (t ≤ L)
    iters: int = 3                # refinement iterations I
    delta: Optional[float] = None  # None → adaptive δ_t rule; float → fixed δ (Exp-3)
    rule: str = "delta_emg"
    align_degree: bool = False    # δ-EMQG: fill every row up to M (Sec. 6.1)
    block: int = 512              # nodes per device batch
    max_hops: int = 1024
    seed: int = 0
    checkpoint_dir: Optional[str] = None


@partial(jax.jit, static_argnames=("rule", "max_keep", "fixed_delta", "t"))
def _select_block(vectors, u_ids, cand_ids, cand_dists, t, rule, max_keep,
                  fixed_delta):
    """Vectorized LocallySelectNeighbors over a block of nodes."""

    def one(u_id, ids, dists):
        u_vec = jnp.take(vectors, u_id, axis=0)
        d2 = jnp.where(ids >= 0, dists * dists, jnp.inf)
        vecs = jnp.take(vectors, jnp.maximum(ids, 0), axis=0)
        if fixed_delta is None:
            deltas = adaptive_deltas(d2, t)
        else:
            deltas = jnp.full(d2.shape, jnp.float32(fixed_delta))
        return select_neighbors(u_vec, vecs, d2, ids, deltas,
                                rule=rule, max_keep=max_keep)

    return jax.vmap(one)(u_ids, cand_ids, cand_dists)


@partial(jax.jit, static_argnames=("rule", "max_keep"))
def _select_block_per_node_t(vectors, u_ids, cand_ids, cand_dists, t_vec,
                             rule, max_keep):
    """Like _select_block but with a per-node t (degree-alignment search)."""

    def one(u_id, ids, dists, t):
        u_vec = jnp.take(vectors, u_id, axis=0)
        d2 = jnp.where(ids >= 0, dists * dists, jnp.inf)
        vecs = jnp.take(vectors, jnp.maximum(ids, 0), axis=0)
        t_idx = jnp.clip(t - 1, 0, d2.shape[0] - 1)
        d_t = jnp.sqrt(jnp.maximum(d2[t_idx], 1e-30))
        deltas = 1.0 - jnp.sqrt(d2) / d_t
        return select_neighbors(u_vec, vecs, d2, ids, deltas,
                                rule=rule, max_keep=max_keep)

    return jax.vmap(one)(u_ids, cand_ids, cand_dists, t_vec)


def _bfs_reachable(neighbors: np.ndarray, start: int) -> np.ndarray:
    """Frontier BFS over fixed-width adjacency.  bool[n]."""
    n = neighbors.shape[0]
    seen = np.zeros(n, bool)
    seen[start] = True
    frontier = np.array([start])
    while frontier.size:
        nxt = neighbors[frontier].ravel()
        nxt = nxt[nxt >= 0]
        nxt = np.unique(nxt)
        nxt = nxt[~seen[nxt]]
        seen[nxt] = True
        frontier = nxt
    return seen


def _add_reverse_edges(nbr: np.ndarray, deg: np.ndarray, M: int) -> None:
    """Line 14: add (v, u) for every (u, v), respecting the degree cap.

    Vectorized form of the sequential rule: visit edges grouped by
    destination u (ascending source within a group) and append source v to
    N(u) unless v == u, v is already in N(u), or N(u) is full."""
    n = nbr.shape[0]
    src = np.repeat(np.arange(n, dtype=np.int64), nbr.shape[1])
    dst = nbr.ravel().astype(np.int64)
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    order = np.argsort(dst, kind="stable")
    u, v = dst[order], src[order]                 # add v into N(u)
    key = u * n + v
    # v already in N(u) ⇔ the edge (u, v) exists; a repeated (u, v) pair
    # counts once (its first visit)
    present = np.isin(key, src * n + dst)
    first = np.zeros(key.size, bool)
    first[np.unique(key, return_index=True)[1]] = True
    cand = (u != v) & ~present & first
    # rank of each candidate within its destination group
    csum = np.cumsum(cand)
    group_start = np.searchsorted(u, u, side="left")
    rank = csum - 1 - (csum[group_start] - cand[group_start])
    take = cand & (rank < M - deg[u])
    nbr[u[take], deg[u[take]] + rank[take]] = v[take]
    deg += np.bincount(u[take], minlength=n).astype(deg.dtype)


def _repair_connectivity(vectors_np: np.ndarray, nbr: np.ndarray,
                         deg: np.ndarray, M: int, med: int,
                         max_rounds: int = 8) -> int:
    """Line 15: link unreachable nodes from their nearest reachable node."""
    n = nbr.shape[0]
    total_fixed = 0
    for _ in range(max_rounds):
        seen = _bfs_reachable(nbr, med)
        bad = np.where(~seen)[0]
        if bad.size == 0:
            break
        good = np.where(seen)[0]
        gv = jnp.asarray(vectors_np[good])
        for s in range(0, bad.size, 1024):
            chunk = bad[s : s + 1024]
            d2 = pairwise_sqdist(jnp.asarray(vectors_np[chunk]), gv)
            nearest = good[np.asarray(jnp.argmin(d2, axis=1))]
            for x, r in zip(chunk.tolist(), nearest.tolist()):
                if deg[r] < M:
                    nbr[r, deg[r]] = x
                    deg[r] += 1
                else:
                    # replace r's longest out-edge (keeps the cap; the evicted
                    # edge is recoverable in the next refinement iteration)
                    row = nbr[r, :M]
                    d2row = ((vectors_np[row] - vectors_np[r]) ** 2).sum(-1)
                    nbr[r, int(np.argmax(d2row))] = x
                total_fixed += 1
    return total_fixed


def _candidate_search(graph: GraphIndex, queries: jax.Array, L: int,
                      max_hops: int):
    """Line 6: R_u ← GreedySearch(G, v_s, u, L, L), returning candidates
    and each query's hop count."""
    p = SearchParams(k=min(L, graph.n), l0=L, l_max=L, adaptive=False,
                     max_hops=max_hops)
    res, cand_ids, cand_dists = search(graph, queries, p, with_candidates=True)
    return cand_ids, cand_dists, res.n_hops


def _reverse_lists(nbr: np.ndarray, cap: int) -> np.ndarray:
    """int32[n, cap] of reverse neighbors (nodes pointing at each row), the
    first ``cap`` sources of each row in ascending order."""
    n, M = nbr.shape
    src = np.repeat(np.arange(n, dtype=np.int32), M)
    dst = nbr.ravel()
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    rank = np.arange(dst.size) - np.searchsorted(dst, dst, side="left")
    keep = rank < cap
    out = np.full((n, cap), -1, np.int32)
    out[dst[keep], rank[keep]] = src[keep]
    return out


def _dedup_rows(ids: np.ndarray, self_ids: np.ndarray) -> np.ndarray:
    """Vectorized per-row dedup: later duplicates (and self) → -1."""
    order = np.argsort(ids, axis=1, kind="stable")
    s = np.take_along_axis(ids, order, axis=1)
    dup = np.zeros_like(s, bool)
    dup[:, 1:] = (s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)
    s = np.where(dup, -1, s)
    out = np.full_like(ids, -1)
    np.put_along_axis(out, order, s, axis=1)
    out[out == self_ids[:, None]] = -1
    return out


@partial(jax.jit, static_argnames=("L",))
def _prep_candidates(vectors, u_ids, merged_ids, L: int):
    """Exact d(u, ·) for merged candidate ids, sorted ascending, top L+1."""

    def one(u_id, ids):
        u_vec = jnp.take(vectors, u_id, axis=0)
        rows = jnp.take(vectors, jnp.maximum(ids, 0), axis=0)
        d2 = jnp.sum((rows - u_vec[None, :]) ** 2, axis=-1)
        d2 = jnp.where(ids >= 0, d2, jnp.inf)
        neg, idx = jax.lax.top_k(-d2, min(L + 1, ids.shape[0]))
        return ids[idx], jnp.sqrt(jnp.maximum(-neg, 0.0))

    return jax.vmap(one)(u_ids, merged_ids)


def _align_degrees(vectors, nbr, deg, cand_ids_all, cand_dists_all,
                   p: BuildParams) -> None:
    """Sec. 6.1: bring every row with fewer than M neighbours up to M
    (FastScan / lane alignment), in place on ``nbr`` and ``deg``.

    With a fixed ``p.delta`` a row keeps every edge the last refinement
    iteration left in it (the δ-selection, the reverse edges and the
    connectivity repair) and its free slots take the node's nearest
    unselected candidates: a δ-monotone greedy step moves to the nearest
    neighbour, and added out-edges can only offer a closer one.  With the
    adaptive δ_t rule the row is re-selected at the smallest t that keeps at
    least M (a binary search on t) and then padded the same way; that drops
    the row's reverse and repair edges, and the repair that follows the
    alignment re-links any node left unreachable.  A row whose candidates
    run out stays short."""
    deficient = np.where(deg < p.max_degree)[0]
    for s in range(0, deficient.size, p.block):
        idx = deficient[s : s + p.block]
        if p.delta is None:
            rows, cnt = _reselect_adaptive_t(
                vectors, idx, cand_ids_all[idx], cand_dists_all[idx], p)
        else:
            rows, cnt = nbr[idx], deg[idx]
        _pad_from_pool(rows, cnt, cand_ids_all[idx], idx)
        nbr[idx] = rows
        deg[idx] = cnt


def _reselect_adaptive_t(vectors, idx, cand_ids, cand_dists,
                         p: BuildParams):
    """The adaptive-δ_t neighbourhoods of nodes ``idx`` at the smallest t
    whose selection keeps at least M (all L where the pool is too small):
    int32[len(idx), M] rows, -1 padded, and their int32 counts."""
    M, L = p.max_degree, p.beam_width
    ids, dst = jnp.asarray(cand_ids), jnp.asarray(cand_dists)
    u_ids = jnp.asarray(idx.astype(np.int32))
    lo = np.full(idx.size, 1, np.int32)
    hi = np.full(idx.size, L, np.int32)
    # nodes with fewer than M candidates can never reach M — take all
    feasible = (cand_ids >= 0).sum(1) >= M + 1
    best = hi.copy()
    for _ in range(int(np.ceil(np.log2(max(L, 2)))) + 1):
        mid = (lo + hi) // 2
        _, cnt = _select_block_per_node_t(
            vectors, u_ids, ids, dst, jnp.asarray(mid),
            rule=p.rule, max_keep=M + 1,
        )
        enough = np.asarray(cnt) >= M
        best = np.where(enough & (mid < best), mid, best)
        hi = np.where(enough, np.maximum(mid - 1, 1), hi)
        lo = np.where(enough, lo, np.minimum(mid + 1, L))
        if (lo > hi).all():
            break
    t_final = np.where(feasible, best, L).astype(np.int32)
    kept, cnt = _select_block_per_node_t(
        vectors, u_ids, ids, dst, jnp.asarray(t_final),
        rule=p.rule, max_keep=M,
    )
    return np.array(kept), np.array(cnt)


def _pad_from_pool(kept: np.ndarray, cnt: np.ndarray, pool: np.ndarray,
                   self_ids: np.ndarray) -> None:
    """Fill each row's free slots (``kept[j, cnt[j]:]``) with its nearest
    unselected candidates, in candidate order; ``pool`` rows are unique ids
    (-1 = empty).  In place on ``kept`` and ``cnt``."""
    M = kept.shape[1]
    kept_mask = np.arange(M)[None, :] < cnt[:, None]
    chosen = ((pool[:, :, None] == kept[:, None, :])
              & kept_mask[:, None, :]).any(axis=2)
    extra = (pool >= 0) & (pool != self_ids[:, None]) & ~chosen
    rank = np.cumsum(extra, axis=1) - 1
    take = extra & (rank < (M - cnt)[:, None])
    rows, cols = np.nonzero(take)
    kept[rows, cnt[rows] + rank[rows, cols]] = pool[rows, cols]
    cnt += take.sum(axis=1).astype(cnt.dtype)


def build_approx(vectors, params: BuildParams = BuildParams(),
                 verbose: bool = False, metrics=None) -> GraphIndex:
    """Algorithm 4.  Returns a localized, degree-balanced approximate δ-EMG.

    ``metrics`` (an ``obs.MetricsRegistry``) receives structured build
    events per phase — bootstrap / refine iterations / degree alignment —
    with nodes/sec and elapsed time; ``verbose`` prints the same records
    for CLI use.  Observation-only: the built graph is identical either way.
    """
    p = params
    vectors = jnp.asarray(vectors, jnp.float32)
    vectors_np = np.asarray(vectors)
    n = vectors.shape[0]
    M, L = p.max_degree, min(p.beam_width, n)
    t_boot = time.perf_counter()
    med = find_medoid(vectors, seed=p.seed)

    # line 2: bootstrap from a top-M approximate NN graph
    _, knn_ids = brute_force_knn(vectors, vectors, min(M, n - 1),
                                 exclude_self=True)
    nbr = np.full((n, M), -1, np.int32)
    nbr[:, : knn_ids.shape[1]] = knn_ids
    graph = GraphIndex(vectors, jnp.asarray(nbr), jnp.int32(med),
                       kind="delta_emg_approx", delta=p.delta or 0.0)

    _build_event(metrics, verbose, "bootstrap", nodes=n,
                 elapsed_s=time.perf_counter() - t_boot,
                 nodes_per_s=n / max(time.perf_counter() - t_boot, 1e-9))

    cand_ids_all = np.full((n, L + 1), -1, np.int32)
    cand_dists_all = np.full((n, L + 1), np.inf, np.float32)

    for it in range(p.iters):
        t0 = time.perf_counter()
        # where the iteration's time goes: candidate search (device, synced
        # by the host copy that follows it), neighbor selection, and the
        # graph surgery on the host; block_hops is each block's lock-step
        # loop length (the slowest query of the block)
        search_s = select_s = 0.0
        block_hops = []
        new_nbr = np.full((n, M), -1, np.int32)
        new_deg = np.zeros(n, np.int32)
        # candidate enrichment: beam-search candidates ∪ current out-neighbors
        # ∪ reverse neighbors (the paper's reverse-edge step, applied at
        # candidate level — standard NSG/Vamana practice; without it the
        # search-only candidate sets of early iterations are anchored near
        # the medoid and clustered data loses inter-cluster navigability).
        cur_nbr = np.asarray(graph.neighbors)
        rev_nbr = _reverse_lists(cur_nbr, M)
        for s in range(0, n, p.block):
            ids_blk = np.arange(s, min(s + p.block, n), dtype=np.int32)
            q_blk = jnp.asarray(vectors_np[ids_blk])
            ts = time.perf_counter()
            cand_ids, cand_dists, hops = _candidate_search(
                graph, q_blk, L, p.max_hops)
            cand_np = np.asarray(cand_ids)
            block_hops.append(int(np.max(np.asarray(hops))))
            search_s += time.perf_counter() - ts
            merged = np.concatenate(
                [cand_np, cur_nbr[ids_blk], rev_nbr[ids_blk]], axis=1)
            merged = _dedup_rows(merged, ids_blk)
            ts = time.perf_counter()
            cand_ids, cand_dists = _prep_candidates(
                vectors, jnp.asarray(ids_blk), jnp.asarray(merged), L)
            kept, cnt = _select_block(
                vectors, jnp.asarray(ids_blk), cand_ids, cand_dists,
                t=min(p.t, L), rule=p.rule, max_keep=M,
                fixed_delta=p.delta,
            )
            new_nbr[ids_blk] = np.asarray(kept)
            new_deg[ids_blk] = np.asarray(cnt)
            select_s += time.perf_counter() - ts
            if it == p.iters - 1:
                cand_ids_all[ids_blk] = np.asarray(cand_ids)
                cand_dists_all[ids_blk] = np.asarray(cand_dists)

        ts = time.perf_counter()
        _add_reverse_edges(new_nbr, new_deg, M)
        n_fixed = _repair_connectivity(vectors_np, new_nbr, new_deg, M, med)
        surgery_s = time.perf_counter() - ts
        graph = GraphIndex(vectors, jnp.asarray(new_nbr), jnp.int32(med),
                           kind="delta_emg_approx", delta=p.delta or 0.0)
        if p.checkpoint_dir:
            os.makedirs(p.checkpoint_dir, exist_ok=True)
            np.savez(os.path.join(p.checkpoint_dir, f"build_iter{it}.npz"),
                     neighbors=new_nbr, medoid=med, iter=it)
        elapsed = time.perf_counter() - t0
        _build_event(metrics, verbose, f"refine_iter{it}", nodes=n,
                     elapsed_s=elapsed, nodes_per_s=n / max(elapsed, 1e-9),
                     mean_deg=float((new_nbr >= 0).sum(1).mean()),
                     repaired=n_fixed, search_s=search_s, select_s=select_s,
                     surgery_s=surgery_s,
                     block_hops_mean=float(np.mean(block_hops)),
                     block_hops_max=max(block_hops))

    if p.align_degree:
        t0 = time.perf_counter()
        deg = (np.asarray(graph.neighbors) >= 0).sum(1).astype(np.int32)
        deficient, before = int((deg < M).sum()), int(deg.sum())
        nbr = np.asarray(graph.neighbors).copy()
        _align_degrees(vectors, nbr, deg, cand_ids_all, cand_dists_all, p)
        # nothing to do after a fixed-δ alignment, which only adds edges
        _repair_connectivity(vectors_np, nbr, deg, M, med)
        graph = GraphIndex(vectors, jnp.asarray(nbr), jnp.int32(med),
                           kind="delta_emqg", delta=p.delta or 0.0)
        elapsed = time.perf_counter() - t0
        _build_event(metrics, verbose, "align_degree", nodes=n,
                     elapsed_s=elapsed, nodes_per_s=n / max(elapsed, 1e-9),
                     deficient=deficient, padded=int(deg.sum()) - before)
    return graph
