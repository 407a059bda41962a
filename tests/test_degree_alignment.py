"""Degree alignment (Sec. 6.1, ``build_approx._align_degrees``): with a
fixed δ it only adds edges, so a row keeps every edge the last refinement
iteration left in it (the δ-selection, the reverse edges and the
connectivity repair) and its free slots are filled up to M from the node's
candidate pool."""

import dataclasses

import numpy as np
import pytest

from repro.core import BuildParams, build_approx
from repro.core.build_approx import _align_degrees

from conftest import gmm


def _short_rows(rng, rows, M, P, n):
    """Rows with a valid prefix, some edges outside the pool (as reverse
    and repair edges are), and nearest-first pools of unique ids."""
    self_ids = rng.choice(n, rows, replace=False).astype(np.int32)
    pool = np.full((rows, P), -1, np.int32)
    nbr = np.full((rows, M), -1, np.int32)
    deg = np.zeros(rows, np.int32)
    for j in range(rows):
        others = np.setdiff1d(np.arange(n), [self_ids[j]])
        m = int(rng.integers(0, P + 1))
        pool[j, :m] = rng.choice(others, m, replace=False)
        if m and rng.random() < 0.2:
            pool[j, int(rng.integers(0, m))] = self_ids[j]   # self in pool
        c = int(rng.integers(0, M + 1))
        nbr[j, :c] = rng.choice(others, c, replace=False)
        deg[j] = c
    return nbr, deg, pool, self_ids


@pytest.mark.parametrize("seed", range(4))
def test_fixed_delta_alignment_keeps_rows_and_fills_nearest_first(seed):
    rng = np.random.default_rng(seed)
    rows, M, P, n = 64, 8, 20, 100
    nbr, deg, pool, self_ids = _short_rows(rng, rows, M, P, n)
    # the pools and rows belong to nodes ``self_ids`` of an n-node graph
    g_nbr = np.full((n, M), -1, np.int32)
    g_deg = np.full(n, M, np.int32)
    g_pool = np.full((n, P), -1, np.int32)
    g_nbr[self_ids], g_deg[self_ids], g_pool[self_ids] = nbr, deg, pool
    before = g_nbr.copy()
    _align_degrees(None, g_nbr, g_deg, g_pool, np.zeros((n, P), np.float32),
                   BuildParams(max_degree=M, beam_width=P - 1, delta=0.2,
                               block=16))
    for j, u in enumerate(self_ids.tolist()):
        c = int(deg[j])
        row = g_nbr[u]
        # every edge stays, in its slot
        np.testing.assert_array_equal(row[:c], before[u, :c])
        cands = [x for x in pool[j].tolist()
                 if x >= 0 and x != u and x not in set(row[:c].tolist())]
        take = cands[: M - c]
        # the free slots take the nearest unselected candidates, in order
        assert row[c : c + len(take)].tolist() == take
        assert (row[c + len(take):] == -1).all()
        assert g_deg[u] == min(M, c + len(cands))
    untouched = np.setdiff1d(np.arange(n), self_ids)
    np.testing.assert_array_equal(g_nbr[untouched], before[untouched])


@pytest.mark.parametrize("delta", [0.2, None])
def test_aligned_build_keeps_every_refined_edge(delta):
    """The same build with and without alignment: rows are full where the
    candidates allow, with a fixed δ every edge of the unaligned graph is in
    the aligned row of its node (the adaptive rule re-selects short rows),
    and the ``align_degree`` event counts the short rows and the edges
    added."""
    from repro.obs import MetricsRegistry

    base = gmm(384, 12, 6, seed=7)
    bp = BuildParams(max_degree=16, beam_width=24, t=16, iters=2,
                     delta=delta, block=128)
    plain = np.asarray(build_approx(base, bp).neighbors)
    reg = MetricsRegistry()
    aligned = np.asarray(build_approx(
        base, dataclasses.replace(bp, align_degree=True),
        metrics=reg).neighbors)
    deg0 = (plain >= 0).sum(1)
    deg1 = (aligned >= 0).sum(1)
    for u in range(plain.shape[0]):
        if delta is not None:
            np.testing.assert_array_equal(aligned[u, : deg0[u]],
                                          plain[u, : deg0[u]])
        assert len(set(aligned[u, : deg1[u]].tolist())) == deg1[u]
        assert u not in aligned[u, : deg1[u]]
    assert (deg0 < 16).any(), "no short row: the case tests nothing"
    assert (deg1 == 16).all()
    ev = [e for e in reg.events if e.get("phase") == "align_degree"]
    assert len(ev) == 1
    assert ev[0]["deficient"] == int((deg0 < 16).sum())
    assert ev[0]["padded"] == int(deg1.sum() - deg0.sum())
