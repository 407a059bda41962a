"""SIFT-shaped corpora and held-out queries, made from a seed.

SIFT1M's descriptors are 128 non-negative integers up to 255 that lie near
a set of low intrinsic dimension.  This generator draws a mixture of
Gaussian clusters in a ``latent_dim``-dimensional space, maps it linearly
into ``dim`` dimensions, shifts it, clips at 0 and rounds to integers up to
255.  Queries are further draws from the same mixture (held out, as SIFT's
query set is).

The corpus and the query set are the deployment's dataset: like SIFT1M's
base set and its 10,000 queries, each is one fixed set of vectors, drawn
from the configuration's ``corpus_seed`` and ``query_seed``.  A run's seed
only orders the queries and draws the arrivals (``traffic.py``), so every
run builds the same index and sends the same queries.

The generator belongs to the benchmark: the program under test receives
only its output.
"""

from __future__ import annotations

import numpy as np


def _mixture(rng, n: int, means: np.ndarray, spread: np.ndarray,
             lift: np.ndarray, offset: np.ndarray, noise: float) -> np.ndarray:
    k, r = means.shape
    asg = rng.integers(0, k, n)
    z = means[asg] + spread[asg, None] * rng.standard_normal((n, r))
    x = offset + z @ lift + noise * rng.standard_normal((n, lift.shape[1]))
    return np.clip(np.rint(x), 0, 255).astype(np.float32)


def sift_like(n: int, queries: int, corpus_seed: int, query_seed: int, *,
              dim: int = 128, latent_dim: int = 16, clusters: int = 64,
              scale: float = 24.0, offset: float = 16.0,
              noise: float = 2.0) -> tuple[np.ndarray, np.ndarray]:
    """(corpus f32[n, dim], queries f32[queries, dim]), integer-valued in
    [0, 255].  The mixture (cluster means, spreads, the linear lift) and the
    corpus come from ``corpus_seed``, the queries from ``query_seed``."""
    rng = np.random.default_rng(corpus_seed)
    means = rng.standard_normal((clusters, latent_dim)) * 1.5
    spread = rng.uniform(0.3, 0.7, clusters)
    lift = rng.standard_normal((latent_dim, dim)) * (scale / np.sqrt(latent_dim))
    # per-dimension shift: a few coordinates sit mostly at 0, as SIFT's do
    shift = offset + rng.uniform(-offset, offset, dim)
    corpus = _mixture(rng, n, means, spread, lift, shift, noise)
    held_out = _mixture(np.random.default_rng([query_seed, 2]), queries,
                        means, spread, lift, shift, noise)
    return corpus, held_out
