"""Filtered (predicate-constrained) error-bounded search.

Production vector stores almost always serve *filtered* queries ("nearest
documents WHERE tenant = t").  On a proximity graph the standard robust
strategy is post-filter-during-traversal: traverse the unfiltered graph
(filtering edges breaks monotonicity and with it the δ-EMG guarantee) but
maintain the result set over passing nodes only, with the candidate window
auto-widened by the filter's selectivity.

The filter is a per-node bitmask (callers precompute it from their
metadata).  The adaptive stop rule (Alg. 3's α) is applied to the
*filtered* candidate list, so the (1/δ′) certificate transfers to the
filtered ground truth whenever the usual local-optimum condition holds for
the unfiltered traversal — the monotonic descent into the δ-neighborhood
is a property of the graph, not of the result filter.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .search import SearchParams, search
from .types import GraphIndex, SearchResult


@partial(jax.jit, static_argnames=("k",))
def _filter_topk(ids, dists, mask, k: int):
    """Keep the k closest candidates whose filter bit is set."""
    ok = jnp.where(ids >= 0, jnp.take(mask, jnp.maximum(ids, 0)), False)
    d = jnp.where(ok, dists, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    out_ids = jnp.take_along_axis(ids, idx, axis=-1)
    out_d = -neg
    return jnp.where(jnp.isfinite(out_d), out_ids, -1), out_d


def filtered_search(graph: GraphIndex, queries, filter_mask, k: int,
                    alpha: float = 1.2, l_max: int = 256,
                    selectivity: Optional[float] = None,
                    max_hops: int = 4096) -> SearchResult:
    """Error-bounded top-k among nodes with ``filter_mask[id] == True``.

    ``selectivity`` (fraction of passing nodes; estimated from the mask when
    omitted) sizes the traversal: the unfiltered search must see ~k/sel
    candidates for k filtered survivors.
    """
    mask = jnp.asarray(filter_mask, bool)
    sel = float(selectivity if selectivity is not None
                else max(float(jnp.mean(mask)), 1e-3))
    k_wide = int(min(l_max, max(k + 4, int(np.ceil(1.5 * k / sel)))))
    p = SearchParams(k=k_wide, l0=k_wide, l_max=max(l_max, k_wide),
                     alpha=alpha, adaptive=True, max_hops=max_hops)
    res, cand_ids, cand_dists = search(graph, jnp.asarray(queries), p,
                                       with_candidates=True)
    ids, dists = _filter_topk(cand_ids, cand_dists, mask, k)
    return dataclasses.replace(res, ids=ids, dists=dists)
