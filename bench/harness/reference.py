"""The plain reference: exact k nearest neighbours in float64, and the
control, the same search computed in bfloat16.

Nothing here imports the program.  The reference is brute force over the
whole corpus, blocked over queries so that a block's distance matrix
stays near ``BLOCK_ELEMS`` float64 values.  It takes the squared distance
as ‖q‖² + ‖x‖² − 2⟨q, x⟩ in float64: on this benchmark's integer-valued
vectors (at most 128·255² ≈ 8.3e6, far below 2⁵³) every term is exact, so
it equals Σ(q − x)² exactly, at a matrix product's speed.  Ties in the
k-th distance are left to the comparison, which counts a returned id as a
true neighbour when its distance is at most the k-th true distance.
"""

from __future__ import annotations

import numpy as np

BLOCK_ELEMS = 1 << 24


def exact_topk_sqdist(corpus: np.ndarray, queries: np.ndarray,
                      k: int) -> np.ndarray:
    """f64[Q, k]: the k smallest squared distances of each query, ascending."""
    x = np.asarray(corpus, np.float64)
    q = np.asarray(queries, np.float64)
    x2 = np.einsum("ij,ij->i", x, x)
    bq = max(1, BLOCK_ELEMS // x.shape[0])
    out = np.empty((q.shape[0], k), np.float64)
    for s in range(0, q.shape[0], bq):
        qb = q[s:s + bq]
        d2 = np.einsum("ij,ij->i", qb, qb)[:, None] + x2[None, :] \
            - 2.0 * (qb @ x.T)
        np.maximum(d2, 0.0, out=d2)
        part = np.partition(d2, k - 1, axis=1)[:, :k]
        out[s:s + bq] = np.sort(part, axis=1)
    return out


def sqdist_of(corpus: np.ndarray, queries: np.ndarray,
              ids: np.ndarray) -> np.ndarray:
    """f64[Q, k]: Σ(q − x_id)² for each returned id (invalid ids → inf)."""
    ok = (ids >= 0) & (ids < corpus.shape[0])
    rows = np.asarray(corpus, np.float64)[np.where(ok, ids, 0)]
    diff = rows - np.asarray(queries, np.float64)[:, None, :]
    d2 = np.einsum("qkd,qkd->qk", diff, diff)
    return np.where(ok, d2, np.inf)


def control_answers(corpus: np.ndarray, queries: np.ndarray, k: int,
                    block: int = 64):
    """The reference in the program's place, in bfloat16: Σ(q − x)² with
    the differences, squares and sums all held in bfloat16, on the default
    JAX device.  Returns (ids int32[Q, k], dists f32[Q, k])."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def topk(xb, qb):
        diff = qb[:, None, :] - xb[None, :, :]
        d2 = jnp.sum(diff * diff, axis=-1, dtype=jnp.bfloat16)
        neg, ids = jax.lax.top_k(-d2, k)
        return ids, jnp.sqrt(jnp.maximum(-neg, 0).astype(jnp.float32))

    xb = jnp.asarray(corpus, jnp.bfloat16)
    ids, dists = [], []
    for s in range(0, queries.shape[0], block):
        qb = np.asarray(queries[s:s + block])
        pad = block - qb.shape[0]
        if pad:
            qb = np.concatenate([qb, np.repeat(qb[-1:], pad, axis=0)])
        i, d = topk(xb, jnp.asarray(qb, jnp.bfloat16))
        ids.append(np.asarray(i)[:block - pad])
        dists.append(np.asarray(d)[:block - pad])
    return np.concatenate(ids).astype(np.int32), np.concatenate(dists)
