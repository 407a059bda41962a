"""A served answer equals the engine's: ``AnnServer`` batches, pads and fans
out requests, and none of that may change a row's ids or distances.

Each case serves the first n queries and compares every answer exactly with
its row of one direct ``search`` / ``probing_search`` call over all the
queries.  The n values fill each bucket (8, 32, 64) exactly, overflow each
by one (padding into the next bucket), and drain in two and three batches;
W = 4 puts duplicate frontier slots into the served path."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BuildParams,
    SearchParams,
    build_approx,
    build_emqg,
    probing_search,
    search,
)
from repro.serve import AnnServer

from conftest import gmm

N_QUERIES = 130
BUILD = BuildParams(max_degree=16, beam_width=32, t=8, iters=1)
INDICES = {
    "emg": (lambda X: build_approx(X, BUILD), search),
    "emqg": (lambda X: build_emqg(X, BUILD), probing_search),  # degree-aligned
}


def _params(w):
    return SearchParams(k=10, l0=10, l_max=64, alpha=1.2, adaptive=True,
                        max_hops=512, beam_width=w)


@functools.lru_cache(maxsize=None)
def _queries():
    return gmm(N_QUERIES, 24, 24, seed=1)


@functools.lru_cache(maxsize=None)
def _index(name):
    return INDICES[name][0](gmm(1200, 24, 24, seed=0))


@functools.lru_cache(maxsize=None)
def _direct(name, w):
    res = INDICES[name][1](_index(name), jnp.asarray(_queries()), _params(w))
    return np.asarray(res.ids), np.asarray(res.dists)


@pytest.mark.parametrize("n", [1, 8, 9, 32, 33, 64, 65, 130])
@pytest.mark.parametrize("w", [1, 4], ids=lambda w: f"W{w}")
@pytest.mark.parametrize("name", list(INDICES))
def test_served_answers_equal_direct_search(name, w, n):
    ids, dists = _direct(name, w)
    srv = AnnServer(_index(name), _params(w), max_batch=64,
                    buckets=(8, 32, 64))
    srv.submit_many(_queries()[:n])
    out = srv.drain()
    assert len(out) == n
    assert srv.stats.n_batches == -(-n // 64)
    for i, (got_ids, got_dists) in enumerate(out):
        np.testing.assert_array_equal(got_ids, ids[i], err_msg=f"row {i}")
        np.testing.assert_array_equal(got_dists, dists[i], err_msg=f"row {i}")
