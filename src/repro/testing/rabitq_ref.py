"""Float64 NumPy reference of the RaBitQ estimator (``core/rabitq.py``),
written from its formula and sharing no code with it.

With centroid ``c`` and an orthogonal rotation ``P`` (an input here, as the
random draw is not part of the estimator):

    r     = P(v − c)                     rotated residual of a vector
    b     = [r > 0]                      its 1-bit code
    ip_xo = Σ|rᵢ| / (√d·‖r‖)             = ⟨x̄, o⟩, x̄ = (2b − 1)/√d, o = r/‖r‖

and for a query with rotated residual ``r_q`` and ``q_u = r_q / ‖r_q‖``:

    d²(v, q) ≈ ‖r‖² + ‖r_q‖² − 2‖r‖‖r_q‖ · ⟨x̄, q_u⟩ / ip_xo

clamped at 0, since a squared distance is never negative.  The probing
engine's decisions rest on these estimates, so the reference pins them.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class RefCodes(NamedTuple):
    bits: np.ndarray     # bool[n, d]
    norms: np.ndarray    # f64[n]   ‖r‖
    ip_xo: np.ndarray    # f64[n]
    r: np.ndarray        # f64[n, d] the rotated residuals
    center: np.ndarray   # f64[d]
    rotation: np.ndarray  # f64[d, d]


def fit(vectors, rotation) -> RefCodes:
    v = np.asarray(vectors, np.float64)
    P = np.asarray(rotation, np.float64)
    center = v.mean(axis=0)
    r = (v - center) @ P.T
    norms = np.sqrt(np.sum(r * r, axis=1))
    ip_xo = np.sum(np.abs(r), axis=1) / (np.sqrt(v.shape[1]) * norms)
    return RefCodes(bits=r > 0, norms=norms, ip_xo=ip_xo, r=r,
                    center=center, rotation=P)


def estimate_sqdist(codes: RefCodes, q, ids) -> np.ndarray:
    """Estimated squared distances f64[m] of query ``q`` to vectors ``ids``."""
    ids = np.asarray(ids)
    d = codes.bits.shape[1]
    r_q = (np.asarray(q, np.float64) - codes.center) @ codes.rotation.T
    norm_q = np.sqrt(np.sum(r_q * r_q))
    x_bar = (2.0 * codes.bits[ids] - 1.0) / np.sqrt(d)
    ip_xq = x_bar @ (r_q / norm_q)
    nv = codes.norms[ids]
    est = nv * nv + norm_q * norm_q \
        - 2.0 * nv * norm_q * ip_xq / codes.ip_xo[ids]
    return np.maximum(est, 0.0)
