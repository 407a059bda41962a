"""MIPS → L2 reduction for inner-product retrieval over a δ-EMG.

Inner-product retrieval maximizes ⟨u, v⟩ while the δ-EMG index answers
min-L2 queries.  The standard exact reduction (Bachrach et al. 2014)
augments items with one extra coordinate:

    φ(v) = [v, √(R² − ‖v‖²)]      R = max‖v‖   (items)
    ψ(u) = [u, 0]                                (queries)

    ‖ψ(u) − φ(v)‖² = ‖u‖² + R² − 2⟨u, v⟩  →  argmin L2 ≡ argmax IP

so a δ-EMG built over φ(items) serves exact-equivalent MIPS, and the
(1/δ′) L2 certificate translates to an additive inner-product bound:
⟨u, v̂⟩ ≥ ⟨u, v*⟩ − (1/δ′² − 1)·d²(ψ(u), φ(v*))/2.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .build_approx import BuildParams, build_approx
from .emqg import build_emqg
from .search import error_bounded_search
from .types import EMQGIndex, GraphIndex, SearchResult


@dataclasses.dataclass
class MIPSIndex:
    index: GraphIndex | EMQGIndex
    radius: float                 # R = max ‖v‖
    dim: int                      # original dimensionality

    @property
    def quantized(self) -> bool:
        return isinstance(self.index, EMQGIndex)


def augment_items(items: np.ndarray) -> tuple[np.ndarray, float]:
    items = np.asarray(items, np.float32)
    norms2 = (items ** 2).sum(-1)
    R2 = float(norms2.max())
    extra = np.sqrt(np.maximum(R2 - norms2, 0.0))[:, None]
    return np.concatenate([items, extra], axis=1), float(np.sqrt(R2))


def augment_queries(queries: np.ndarray) -> np.ndarray:
    queries = np.asarray(queries, np.float32)
    return np.concatenate(
        [queries, np.zeros((queries.shape[0], 1), np.float32)], axis=1)


def build_mips(items: np.ndarray, params: Optional[BuildParams] = None,
               quantized: bool = False) -> MIPSIndex:
    aug, R = augment_items(items)
    params = params or BuildParams()
    idx = build_emqg(aug, params) if quantized else build_approx(aug, params)
    return MIPSIndex(index=idx, radius=R, dim=items.shape[1])


def mips_search(mips: MIPSIndex, queries: np.ndarray, k: int,
                alpha: float = 1.2, l_max: int = 256) -> SearchResult:
    """Top-k by inner product (ids are item rows; dists are the reduced-L2
    distances — convert with ``ip_from_l2`` if scores are needed)."""
    aug_q = jnp.asarray(augment_queries(queries))
    if mips.quantized:
        from .probing import error_bounded_probing_search

        return error_bounded_probing_search(mips.index, aug_q, k=k,
                                            alpha=alpha, l_max=l_max)
    return error_bounded_search(mips.index, aug_q, k=k, alpha=alpha,
                                l_max=l_max)


def ip_from_l2(queries: np.ndarray, l2_dists, radius: float):
    """⟨u, v⟩ = (‖u‖² + R² − d²)/2 — recover scores from reduced distances."""
    q2 = (np.asarray(queries, np.float32) ** 2).sum(-1, keepdims=True)
    d2 = np.asarray(l2_dists) ** 2
    return (q2 + radius ** 2 - d2) / 2.0
