"""δ-EMQG assembly (Sec. 6.1): approximate δ-EMG + RaBitQ codes with
degree-aligned neighborhoods.

The paper aligns every out-degree to a multiple of the AVX2 FastScan batch
(32) so no SIMD lanes are wasted.  The TPU analogue: neighbor lists are
filled up to exactly ``M`` where the candidate pool allows (with a fixed δ,
keeping every edge the refinement chose; ``build_approx._align_degrees``),
and ``M`` itself should be a multiple of the 8-row sublane tile so the
bitdot/gather kernels run full tiles.
Codes are stored as one global row-major matrix — the CPU version duplicates
codes per-neighborhood for cache locality, which on TPU would multiply HBM
footprint ×M for no DMA benefit (rows are fetched by scalar-prefetch
indexing either way); this deviation is recorded in DESIGN.md §2.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from . import rabitq
from .build_approx import BuildParams, _build_event, build_approx
from .types import EMQGIndex, GraphIndex


def build_emqg(vectors, params: Optional[BuildParams] = None,
               key: Optional[jax.Array] = None, verbose: bool = False,
               metrics=None) -> EMQGIndex:
    """Full δ-EMQG build: Algorithm 4 with degree alignment + RaBitQ codes.
    ``metrics``/``verbose`` forward to ``build_approx`` (structured build
    progress events through the obs registry); the RaBitQ fit adds a
    ``quantize`` event."""
    if params is None:
        params = BuildParams(align_degree=True)
    elif not params.align_degree:
        params = dataclasses.replace(params, align_degree=True)
    if key is None:
        key = jax.random.PRNGKey(params.seed)
    graph = build_approx(vectors, params, verbose=verbose, metrics=metrics)
    t0 = time.perf_counter()
    codes = jax.block_until_ready(rabitq.fit(graph.vectors, key))
    _build_event(metrics, verbose, "quantize", nodes=graph.n,
                 elapsed_s=time.perf_counter() - t0)
    return EMQGIndex(graph=graph, codes=codes)


def from_graph(graph: GraphIndex, key: Optional[jax.Array] = None) -> EMQGIndex:
    """Attach RaBitQ codes to an existing graph (ablation δ-EMQG-NSG etc.)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    return EMQGIndex(graph=graph, codes=rabitq.fit(graph.vectors, key))


def memory_footprint(index: EMQGIndex) -> dict:
    """Bytes per component — the paper's Fig. 4 'index size' accounting."""
    g, c = index.graph, index.codes
    return {
        "vectors": g.vectors.size * g.vectors.dtype.itemsize,
        "adjacency": g.neighbors.size * 4,
        "codes": c.codes.size * 4,
        "code_scalars": (c.norms.size + c.ip_xo.size) * 4,
        "rotation": c.rotation.size * 4,
    }
