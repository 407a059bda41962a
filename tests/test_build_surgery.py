"""The build's host-side graph surgery (``core/build_approx.py``) against
the sequential loops it replaced: reverse edges under the degree cap,
reverse-neighbor lists, and degree-alignment padding must give equal
arrays on random graphs."""

import numpy as np
import pytest

from repro.core.build_approx import (
    _add_reverse_edges,
    _pad_from_pool,
    _reverse_lists,
)


def _add_reverse_edges_loop(nbr, deg, M):
    n = nbr.shape[0]
    src = np.repeat(np.arange(n, dtype=np.int32), nbr.shape[1])
    dst = nbr.ravel()
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    for u, v in zip(dst.tolist(), src.tolist()):     # add v into N(u)
        if deg[u] >= M:
            continue
        row = nbr[u, : deg[u]]
        if v == u or (row == v).any():
            continue
        nbr[u, deg[u]] = v
        deg[u] += 1


def _reverse_lists_loop(nbr, cap):
    n, M = nbr.shape
    src = np.repeat(np.arange(n, dtype=np.int32), M)
    dst = nbr.ravel()
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    out = np.full((n, cap), -1, np.int32)
    starts = np.searchsorted(dst, np.arange(n))
    ends = np.searchsorted(dst, np.arange(n) + 1)
    for u in range(n):
        take = src[starts[u] : ends[u]][:cap]
        out[u, : take.size] = take
    return out


def _pad_from_pool_loop(kept, cnt, pool, self_ids):
    M = kept.shape[1]
    for j in range(self_ids.size):
        row, c = kept[j], int(cnt[j])
        if c < M:
            p = pool[j]
            p = p[(p >= 0) & (p != self_ids[j])]
            extra = [x for x in p.tolist() if x not in set(row[:c].tolist())]
            take = extra[: M - c]
            row[c : c + len(take)] = take
            cnt[j] = c + len(take)


def _random_graph(rng, n, M):
    nbr = np.full((n, M), -1, np.int32)
    deg = np.zeros(n, np.int32)
    for u in range(n):
        c = int(rng.integers(0, M + 1))
        ch = rng.choice(n, size=min(c, n), replace=False)
        if rng.random() < 0.1 and len(ch):
            ch[0] = u                                # occasional self edge
        nbr[u, : len(ch)] = ch
        deg[u] = len(ch)
    return nbr, deg


@pytest.mark.parametrize("seed", range(6))
def test_reverse_edges_and_lists_equal_loops(seed):
    rng = np.random.default_rng(seed)
    n, M = int(rng.integers(5, 300)), int(rng.integers(2, 12))
    nbr, deg = _random_graph(rng, n, M)
    a, da = nbr.copy(), deg.copy()
    _add_reverse_edges_loop(a, da, M)
    b, db = nbr.copy(), deg.copy()
    _add_reverse_edges(b, db, M)
    np.testing.assert_array_equal(b, a)
    np.testing.assert_array_equal(db, da)
    cap = int(rng.integers(1, 10))
    np.testing.assert_array_equal(_reverse_lists(nbr, cap),
                                  _reverse_lists_loop(nbr, cap))


@pytest.mark.parametrize("seed", range(4))
def test_pad_from_pool_equals_loop(seed):
    rng = np.random.default_rng(seed)
    rows, M, P, n = 40, 8, 20, 60
    self_ids = rng.choice(n, rows, replace=False).astype(np.int32)
    pool = np.full((rows, P), -1, np.int32)
    kept = np.full((rows, M), -1, np.int32)
    cnt = np.zeros(rows, np.int32)
    for j in range(rows):
        m = int(rng.integers(0, P + 1))
        pool[j, :m] = rng.choice(n, m, replace=False)
        sel = [x for x in pool[j, :m] if x != self_ids[j]]
        c = int(rng.integers(0, min(M, len(sel)) + 1))
        kept[j, :c] = rng.choice(sel, c, replace=False) if c else []
        cnt[j] = c
    a, ca = kept.copy(), cnt.copy()
    _pad_from_pool_loop(a, ca, pool, self_ids)
    b, cb = kept.copy(), cnt.copy()
    _pad_from_pool(b, cb, pool, self_ids)
    np.testing.assert_array_equal(b, a)
    np.testing.assert_array_equal(cb, ca)
