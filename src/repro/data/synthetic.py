"""Deterministic synthetic vector corpora (offline container — no dataset
downloads).  ``clustered_vectors`` is a pure function of its seed: SIFT-like
corpora for the ANN core, a Gaussian mixture with overlapping clusters plus
a uniform noise floor (LID roughly tunable via scale / n_clusters)."""

from __future__ import annotations

import numpy as np


def clustered_vectors(n: int, dim: int, n_clusters: int = 64,
                      scale: float = 0.35, noise_frac: float = 0.05,
                      seed: int = 0) -> np.ndarray:
    """Overlapping GMM + uniform noise floor; unit-ish norm spread."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, dim)).astype(np.float32)
    n_noise = int(n * noise_frac)
    asg = rng.integers(0, n_clusters, n - n_noise)
    pts = centers[asg] + scale * rng.normal(size=(n - n_noise, dim))
    noise = rng.normal(size=(n_noise, dim)) * 1.2
    out = np.concatenate([pts, noise]).astype(np.float32)
    rng.shuffle(out)
    return out
