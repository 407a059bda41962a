"""Core pytree datatypes for the δ-EMG framework.

All index structures are JAX pytrees so they can be donated, sharded with
``NamedSharding`` and passed through ``jit``/``shard_map`` unchanged.  Static
hyper-parameters (degree cap, δ, …) live in the aux data so retracing only
happens when the *shape* of the index changes, never per query.

Conventions
-----------
* Neighbor lists are fixed-width ``int32[n, M]`` padded with ``INVALID_ID``.
* Distances are *squared* Euclidean internally (monotone in true distance);
  public APIs report true distances.  Squared form saves an rsqrt per
  candidate in the hot loop and keeps the occlusion predicates polynomial.
* ``INVALID_ID = -1``; invalid slots always carry ``+inf`` distance.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

INVALID_ID = jnp.int32(-1)
INF = jnp.float32(jnp.inf)


def _register(cls):
    """Register a dataclass as a pytree, splitting array/static fields."""
    data_fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    meta_fields = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]
    return jax.tree_util.register_dataclass(cls, data_fields, meta_fields)


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@_register
@dataclasses.dataclass(frozen=True)
class GraphIndex:
    """A proximity graph over a vector dataset.

    Attributes
    ----------
    vectors:   ``f32[n, d]`` the base dataset (row ``i`` = vector of node ``i``).
    neighbors: ``int32[n, M]`` fixed-width adjacency, padded with ``INVALID_ID``.
    medoid:    ``int32[]`` default entry point for searches.
    kind:      static tag — "delta_emg" | "mrng" | "tau_mg" | "vamana" |
               "nsw" | "knn" (used for reporting only).
    delta:     static — the construction δ (0 for rule families without one).
    """

    vectors: jax.Array
    neighbors: jax.Array
    medoid: jax.Array
    kind: str = static_field(default="delta_emg")
    delta: float = static_field(default=0.0)

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def max_degree(self) -> int:
        return self.neighbors.shape[1]

    def degrees(self) -> jax.Array:
        return jnp.sum(self.neighbors >= 0, axis=1)


@_register
@dataclasses.dataclass(frozen=True)
class RaBitQCodes:
    """RaBitQ 1-bit-per-dimension quantization state.

    ``codes`` packs sign bits of the rotated, centered vectors 32-dims per
    uint32 lane (little-endian within the lane:  bit ``j`` of word ``w``
    is dimension ``32*w + j``).

    Per-vector scalars required by the unbiased estimator:
      * ``norms``  — ``‖v − c‖``            (f32[n])
      * ``ip_xo``  — ``⟨x̄, o⟩``             (f32[n]) where ``o=(v−c)/‖v−c‖``
                     and ``x̄ = sign(P(v−c))/√d``.
    ``rotation`` is the shared orthogonal matrix ``P`` (f32[d, d]) and
    ``center`` the shared centroid ``c`` (f32[d]).
    """

    codes: jax.Array        # uint32[n, ceil(d/32)]
    norms: jax.Array        # f32[n]
    ip_xo: jax.Array        # f32[n]
    rotation: jax.Array     # f32[d, d]
    center: jax.Array       # f32[d]
    dim: int = static_field(default=0)

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    @property
    def words(self) -> int:
        return self.codes.shape[1]


@_register
@dataclasses.dataclass(frozen=True)
class EMQGIndex:
    """δ-EMQG = δ-EMG graph + RaBitQ codes (Sec. 6 of the paper)."""

    graph: GraphIndex
    codes: RaBitQCodes

    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def dim(self) -> int:
        return self.graph.dim


@_register
@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Batched search output.

    ids / dists are ``[B, k]`` (true Euclidean distances, ascending).
    ``n_dist_comps`` counts *exact* distance evaluations per query — the
    paper's Exp-5 efficiency metric.  ``n_approx_comps`` counts quantized
    evaluations (δ-EMQG only).  ``n_hops`` counts expansions.
    ``n_encounters`` counts candidate *encounters*: every valid neighbor id
    produced by an expansion (plus every probed candidate, for the probing
    engine) *before* dedup.  The beam engine's packed bitset never
    re-evaluates a pruned-then-reencountered node, so its ``n_dist_comps``
    undercounts relative to the paper's Exp-5 counter; ``n_encounters`` is
    dedup-independent and identical across engines at ``beam_width=1``.
    ``saturated`` flags queries whose adaptive ``l`` hit the buffer cap
    before the α-stop rule fired (bound may not hold for those).
    ``n_iters`` counts the lock-step loop iterations each row was active in;
    its largest value over the batch's rows is the loop's trip count.  A
    result made without the loop leaves it ``None``.  ``n_resident`` counts
    the iterations in which the row held a slot in the loop's batch, active
    or finished: the loop compacts its batch as rows finish, so its sum over
    the rows is the row iterations the loop ran (``None`` without the
    loop, as ``n_iters``).  ``n_probes`` counts
    the candidates the probing engine promoted to its exact tier; the other
    engines leave it ``None``.
    """

    ids: jax.Array
    dists: jax.Array
    n_dist_comps: jax.Array
    n_approx_comps: jax.Array
    n_hops: jax.Array
    final_l: jax.Array
    saturated: jax.Array
    n_encounters: jax.Array = None
    n_iters: jax.Array = None
    n_probes: jax.Array = None
    n_resident: jax.Array = None


@_register
@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Static search hyper-parameters (hashable → one trace per setting)."""

    k: int = static_field(default=10)
    l0: int = static_field(default=16)          # initial candidate width (≥ k)
    l_max: int = static_field(default=128)      # buffer capacity / adaptive cap
    l_step: int = static_field(default=1)       # adaptive growth per outer round
    alpha: float = static_field(default=1.0)    # α stop rule (Alg. 3); 1.0 = greedy
    adaptive: bool = static_field(default=False)  # False → Alg. 1, True → Alg. 3
    max_hops: int = static_field(default=512)   # hard iteration cap (also T ring size)
    rerank: bool = static_field(default=True)   # δ-EMQG: exact rerank of results
    beam_width: int = static_field(default=1)   # frontier nodes expanded per hop (W)


def take_rows(mat: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather rows with INVALID_ID-safe indexing (invalid → row 0, caller masks)."""
    safe = jnp.where(ids >= 0, ids, 0)
    return jnp.take(mat, safe, axis=0)


@partial(jax.jit, static_argnames=("k",))
def topk_smallest(dists: jax.Array, ids: jax.Array, k: int):
    """Return the k smallest (dist, id) pairs, ascending, along the last axis."""
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, jnp.take_along_axis(ids, idx, axis=-1)
