"""End-to-end behaviour of the paper's system: build → serve → validate the
error-bounded contract, baselines included (the 'does the whole thing hang
together' test)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BuildParams,
    SearchParams,
    baselines,
    build_approx,
    build_emqg,
    error_bounded_probing_search,
    error_bounded_search,
    greedy_search,
)
from repro.core.distances import brute_force_knn
from repro.data import clustered_vectors

from conftest import recall_at_k


@pytest.fixture(scope="module")
def corpus():
    # d=48 gives RaBitQ its O(1/√d) headroom; moderate cluster overlap
    # (scale 0.6) matches the paper's dataset LID range
    base = clustered_vectors(1500, 48, 24, seed=5, scale=0.6)
    queries = clustered_vectors(48, 48, 24, seed=6, scale=0.6)
    gt_d, gt_i = brute_force_knn(queries, base, 10)
    return base, queries, gt_d, gt_i


def test_full_pipeline_emg(corpus):
    base, queries, gt_d, gt_i = corpus
    g = build_approx(base, BuildParams(max_degree=24, beam_width=64, t=40,
                                       iters=3, block=512))
    res = error_bounded_search(g, jnp.asarray(queries), k=10, alpha=2.0,
                               l_max=192)
    rec = recall_at_k(res.ids, gt_i, 10)
    assert rec > 0.9
    # relative distance error small in aggregate (Exp-5's metric)
    dists = np.asarray(res.dists)
    rde = (dists - gt_d) / np.maximum(gt_d, 1e-9)
    assert rde.mean() < 0.02
    assert (rde >= -1e-4).all()        # can never beat the ground truth


def test_full_pipeline_emqg(corpus):
    base, queries, gt_d, gt_i = corpus
    idx = build_emqg(base, BuildParams(max_degree=24, beam_width=64, t=40,
                                       iters=2, block=512, align_degree=True))
    res = error_bounded_probing_search(idx, jnp.asarray(queries), k=10,
                                       alpha=2.0, l_max=192)
    assert recall_at_k(res.ids, gt_i, 10) > 0.75
    # quantized search must do most distance work in the approximate tier
    assert (np.asarray(res.n_approx_comps) >
            np.asarray(res.n_dist_comps)).mean() > 0.9


@pytest.mark.parametrize("builder", ["nsg", "vamana", "tau_mg"])
def test_baseline_builders_serve(corpus, builder):
    base, queries, gt_d, gt_i = corpus
    g = baselines.BUILDERS[builder](base, max_degree=24, beam_width=48)
    res = greedy_search(g, jnp.asarray(queries), k=10, l=64)
    rec = recall_at_k(res.ids, gt_i, 10)
    assert rec > 0.6, (builder, rec)


def test_knn_graph_lacks_navigability(corpus):
    """Motivating observation: a plain kNN graph has no inter-cluster
    navigability — greedy search from the medoid strands in one cluster.
    The occlusion-rule graphs exist precisely to fix this."""
    base, queries, gt_d, gt_i = corpus
    g_knn = baselines.build_knn_graph(base, k=24)
    g_emg = __import__("repro.core", fromlist=["build_approx"]).build_approx(
        base, BuildParams(max_degree=24, beam_width=64, t=40, iters=2,
                          block=512))
    r_knn = recall_at_k(greedy_search(g_knn, jnp.asarray(queries), k=10,
                                      l=96).ids, gt_i, 10)
    r_emg = recall_at_k(greedy_search(g_emg, jnp.asarray(queries), k=10,
                                      l=96).ids, gt_i, 10)
    assert r_emg > r_knn + 0.3


def test_nsw_baseline(corpus):
    base, queries, gt_d, gt_i = corpus
    g = baselines.build_nsw(base, max_degree=24, ef=48, wave=256)
    res = greedy_search(g, jnp.asarray(queries), k=10, l=64)
    assert recall_at_k(res.ids, gt_i, 10) > 0.45


def test_clustered_vectors_shape_and_spread():
    X = clustered_vectors(500, 16, 10, seed=1)
    assert X.shape == (500, 16) and np.isfinite(X).all()
    assert X.std() > 0.5


def test_ann_server_batching(small_corpus):
    from repro.core import BuildParams, SearchParams, build_approx
    from repro.serve import AnnServer

    g = build_approx(small_corpus["base"],
                     BuildParams(max_degree=16, beam_width=32, t=8, iters=1))
    srv = AnnServer(g, SearchParams(k=5, l0=8, l_max=32, adaptive=False,
                                    max_hops=256), max_batch=16,
                    buckets=(4, 16))
    srv.submit_many(small_corpus["queries"][:23])
    out = srv.drain()
    assert len(out) == 23
    assert srv.stats.n_batches == 2
    ids0, d0 = out[0]
    assert ids0.shape == (5,) and (np.diff(d0) >= -1e-5).all()


def test_ann_smoke_config():
    """The paper's own (sift1m) smoke config builds + serves end to end."""
    from repro.configs import sift1m

    sc = sift1m.SMOKE
    X = clustered_vectors(sc["n"], sc["dim"], 32, seed=0)
    idx = build_emqg(X, sc["build"])
    res = error_bounded_probing_search(
        idx, jnp.asarray(X[:16] + 0.01), k=sc["search"].k,
        alpha=sc["search"].alpha, l_max=sc["search"].l_max)
    assert res.ids.shape == (16, sc["search"].k)
    assert bool(jnp.all(jnp.isfinite(res.dists)))
