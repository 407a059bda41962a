"""The comparison that decides ``correct``.

Every answer due in the window is judged against the float64 reference by
what it says: the ids it returns, in order, with their distances.

* ``missing``: answers due that never came.  Limit 0.
* ``bad_answers``: answers with an id outside the corpus, a repeated id, a
  distance that is not finite, or distances out of ascending order.
  Limit 0.
* ``dist_err``: the widest gap between a returned distance and the float64
  distance of its id, relative to that distance (distances under 1 compare
  absolutely: distinct integer-valued vectors lie at least 1 apart).  The
  limit is set from the program's readings and the control's (PERF.md).
* ``bound_ratio``: the largest ratio, over every answer and rank, of the
  float64 distance of the returned id to the true distance at that rank.
  The configuration states its limit, 1/δ.

``recall_at_10`` is reported beside them as an end-to-end metric and is not
compared: a search that answers within the guarantees is correct.
"""

from __future__ import annotations

import numpy as np

from . import reference


def judge(corpus, queries, qidx, ids, dists, k: int, limits: dict,
          n_due: int) -> dict:
    """Readings and verdict for the answers ``ids``/``dists`` [A, k] given
    to queries ``queries[qidx]``; ``n_due`` answers were due."""
    ids = np.asarray(ids).reshape(-1, k).astype(np.int64)
    dists = np.asarray(dists, np.float64).reshape(-1, k)
    qidx = np.asarray(qidx, np.int64)
    n = corpus.shape[0]
    uq, inv = np.unique(qidx, return_inverse=True)
    true_top = reference.exact_topk_sqdist(corpus, queries[uq], k)[inv]
    true_d2 = reference.sqdist_of(corpus, queries[qidx], ids)

    valid = (ids >= 0) & (ids < n)
    srt = np.sort(np.where(valid, ids, -1 - np.arange(k)), axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    finite = np.isfinite(dists).all(axis=1)
    with np.errstate(invalid="ignore"):
        ordered = (np.diff(dists, axis=1) >= 0).all(axis=1)
    bad = ~valid.all(axis=1) | dup | ~finite | ~ordered
    good = ~bad

    d_true = np.sqrt(true_d2[good])
    gap = np.abs(dists[good] - d_true) / np.maximum(d_true, 1.0)
    dist_err = float(gap.max()) if gap.size else 0.0

    d_star = np.sqrt(true_top[good])
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(d_star > 0, d_true / d_star,
                         np.where(d_true > 0, np.inf, 1.0))
    bound_ratio = float(ratio.max()) if ratio.size else 0.0

    kth = true_top[:, k - 1:k]
    hits = np.where(good[:, None], true_d2 <= kth, False)
    recall = float(hits.sum()) / max(ids.shape[0] * k, 1)

    readings = {
        "missing": max(n_due - ids.shape[0], 0),
        "bad_answers": int(bad.sum()),
        "dist_err": dist_err,
        "bound_ratio": bound_ratio,
    }
    checks = {name: {"value": readings[name], "limit": limits[name]}
              for name in readings}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": correct, "checks": checks, "recall": recall,
            "failed": readings["missing"] + readings["bad_answers"]}
