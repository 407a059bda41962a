"""Search-engine behaviour: recall, adaptivity, counters, invariants."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BuildParams,
    SearchParams,
    build_approx,
    error_bounded_search,
    greedy_search,
    search,
)

from conftest import recall_at_k


@pytest.fixture(scope="module")
def approx_graph(small_corpus):
    p = BuildParams(max_degree=24, beam_width=48, t=24, iters=3, block=512)
    return build_approx(small_corpus["base"], p)


def test_recall_reasonable(approx_graph, small_corpus):
    res = error_bounded_search(approx_graph,
                               jnp.asarray(small_corpus["queries"]),
                               k=10, alpha=2.0, l_max=128)
    assert recall_at_k(res.ids, small_corpus["gt_i"], 10) > 0.85


def test_greedy_l_monotone_recall(approx_graph, small_corpus):
    """Wider greedy beams can only help recall (within noise)."""
    rs = []
    for l in (10, 32, 96):
        res = greedy_search(approx_graph, jnp.asarray(small_corpus["queries"]),
                            k=10, l=l)
        rs.append(recall_at_k(res.ids, small_corpus["gt_i"], 10))
    assert rs[0] <= rs[1] + 0.05 and rs[1] <= rs[2] + 0.05
    assert rs[2] > 0.85


def test_alpha_widens_search(approx_graph, small_corpus):
    """Larger α ⇒ stricter stop ⇒ monotonically more work (Alg. 3)."""
    work = []
    for alpha in (1.0, 1.15, 1.4):
        res = error_bounded_search(
            approx_graph, jnp.asarray(small_corpus["queries"]),
            k=10, alpha=alpha, l_max=128)
        work.append(float(np.mean(np.asarray(res.n_dist_comps))))
    assert work[0] <= work[1] <= work[2]


def test_results_sorted_and_valid(approx_graph, small_corpus):
    res = error_bounded_search(approx_graph,
                               jnp.asarray(small_corpus["queries"]),
                               k=10, alpha=1.5, l_max=96)
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    n = small_corpus["base"].shape[0]
    assert ((ids >= 0) & (ids < n)).all()
    assert (np.diff(dists, axis=1) >= -1e-5).all()
    # distances are true Euclidean distances
    rows = small_corpus["base"][ids.ravel()].reshape(ids.shape + (-1,))
    expect = np.linalg.norm(rows - small_corpus["queries"][:, None, :], axis=-1)
    np.testing.assert_allclose(dists, expect, rtol=1e-4, atol=1e-4)


def test_no_duplicate_results(approx_graph, small_corpus):
    res = error_bounded_search(approx_graph,
                               jnp.asarray(small_corpus["queries"]),
                               k=10, alpha=1.5, l_max=96)
    ids = np.asarray(res.ids)
    for row in ids:
        assert len(set(row.tolist())) == len(row)


def test_deterministic(approx_graph, small_corpus):
    q = jnp.asarray(small_corpus["queries"])
    r1 = error_bounded_search(approx_graph, q, k=10, alpha=1.3, l_max=96)
    r2 = error_bounded_search(approx_graph, q, k=10, alpha=1.3, l_max=96)
    assert (np.asarray(r1.ids) == np.asarray(r2.ids)).all()


def test_counters_consistent(approx_graph, small_corpus):
    res = error_bounded_search(approx_graph,
                               jnp.asarray(small_corpus["queries"]),
                               k=10, alpha=1.3, l_max=96)
    n_dist = np.asarray(res.n_dist_comps)
    hops = np.asarray(res.n_hops)
    M = approx_graph.max_degree
    assert (n_dist >= hops).all()            # ≥1 per expansion + start
    assert (n_dist <= hops * M + 1).all()    # ≤ M per expansion


def test_faithful_prune_variant_runs(approx_graph, small_corpus):
    p = SearchParams(k=10, l0=10, l_max=96, alpha=1.3, adaptive=True,
                     max_hops=1024)
    res = search(approx_graph, jnp.asarray(small_corpus["queries"]), p,
                 faithful_prune=True)
    assert recall_at_k(res.ids, small_corpus["gt_i"], 10) > 0.4


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 10), alpha=st.floats(1.0, 2.0))
def test_property_topk_prefix_consistency(approx_graph, small_corpus, k, alpha):
    """R_j(q) for j < k is a prefix of R_k(q) distances (non-decreasing)."""
    res = error_bounded_search(approx_graph,
                               jnp.asarray(small_corpus["queries"][:8]),
                               k=k, alpha=alpha, l_max=64)
    d = np.asarray(res.dists)
    assert d.shape[1] == k
    assert (np.diff(d, axis=1) >= -1e-5).all()
