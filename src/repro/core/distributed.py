"""Distributed sharded ANN index — the multi-pod serving path.

Standard scale-out ANN architecture (SPANN/DiskANN-style), expressed in
``shard_map``:

* Dataset rows are partitioned into S shards; each shard holds an
  independent δ-EMG / δ-EMQG over its rows (local id space + global offset).
* A query batch is replicated across the index-sharding axes and sharded
  across the ``pod`` axis (each pod serves its own slice of the request
  stream against a full index replica-set).
* Every device runs the *same* lock-step batched search over its shard, then
  the per-shard top-k are merged exactly:
    - ``merge="all_gather"``: one all-gather of (k ids, k dists) + local
      top-k — one collective, O(S·k·B) bytes per device.
    - ``merge="ring"``: S−1 ``ppermute`` steps each merging two k-lists —
      O((S−1)·k·B) bytes total but pipelined on neighbor links only; this is
      the collective-term optimization evaluated in EXPERIMENTS.md §Perf.

Exactness: top-k over a union of disjoint sets == merge of per-set top-k, so
sharding never loses recall (per-shard search quality is the only
approximation, same as the single-node index).

All index containers are pytrees → ``stack_indices`` builds the [S, ...]
stacked representation with ``tree_map``, and the same code path serves
GraphIndex (Alg. 3) and EMQGIndex (Alg. 5).

Fault tolerance: ``run`` accepts a per-slot validity mask.  A dead slot's
candidates are rewritten to (id=-1, dist=inf) *before* the merge, so both
merge strategies exclude them without a second collective.  The host-side
``ShardHealthRegistry`` tracks per-replica liveness and derives the mask:
with replica groups (``build_replicated``, slot layout ``s·R + r``) exactly
one live replica per logical shard participates — a lost primary fails over
to its replica before coverage degrades at all.  When every replica of a
shard is gone, ``FaultTolerantShardedSearch`` still answers, but each
response carries explicit degradation accounting — ``coverage =
live_shards/S`` and ``max_missed = min(k, Σ_dead min(k, |shard|))``, the
worst case being all of a dead shard's top-k members belonging to the true
global top-k (mirrors the ``1/(δ·α)`` bound reporting in
``serve/resilience.py``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .build_approx import BuildParams, build_approx
from .emqg import build_emqg
from .probing import probing_search
from .search import search
from .types import EMQGIndex, GraphIndex, SearchParams, static_field, _register


@_register
@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """Stacked per-shard indexes + global id offsets.

    ``index`` leaves have leading dim S.  ``offsets`` is int32[S] — global id
    of local row 0 in each shard.  Shards must be equal-sized (pad the last
    shard by repeating its first row).  ``sizes`` is int32[S] — the number of
    *real* (non-pad) rows in each slot: local ids ``>= sizes[s]`` are pad
    copies of local row 0, and the merge masks them out exactly like
    dead-shard candidates (``id=-1, dist=inf``) — a pad can never leak a
    global id ``>= n_total`` or duplicate its source row's id (the source
    row itself competes in the same local top-k at the same distance).
    ``sizes=None`` (legacy / abstract indexes) treats every row as real.
    """

    index: GraphIndex | EMQGIndex
    offsets: jax.Array
    n_total: int = static_field(default=0)
    sizes: Optional[jax.Array] = None

    @property
    def n_shards(self) -> int:
        return self.offsets.shape[0]

    @property
    def dim(self) -> int:
        g = self.index.graph if isinstance(self.index, EMQGIndex) else self.index
        return int(g.vectors.shape[-1])

    @property
    def delta(self) -> float:
        g = self.index.graph if isinstance(self.index, EMQGIndex) else self.index
        return float(getattr(g, "delta", 0.0))


def stack_indices(indices: Sequence, offsets: Sequence[int], n_total: int,
                  sizes: Optional[Sequence[int]] = None) -> ShardedIndex:
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *indices)
    offsets = jnp.asarray(offsets, jnp.int32)
    if sizes is None:
        # contiguous-partition default: real rows per shard = what remains of
        # n_total past the shard's offset, clipped to the slot capacity
        g = indices[0].graph if isinstance(indices[0], EMQGIndex) else indices[0]
        per = int(g.vectors.shape[0])
        sizes = jnp.clip(n_total - offsets, 0, per)
    return ShardedIndex(index=stacked,
                        offsets=offsets,
                        n_total=n_total,
                        sizes=jnp.asarray(sizes, jnp.int32))


def slot_sharding(mesh, shard_axes=("data",)) -> NamedSharding:
    """``NamedSharding(mesh, P(shard_axes))`` on an Auto-typed view of
    ``mesh``: the slot layout stays a placement and never enters the
    arrays' types (on an Explicit mesh, as ``jax.make_mesh`` builds, every
    later op on one slot would need a mesh context)."""
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return NamedSharding(auto, P(shard_axes))


def place_sharded(sidx: ShardedIndex, mesh,
                  shard_axes=("data",)) -> ShardedIndex:
    """Commit every leaf of ``sidx`` to ``slot_sharding(mesh, shard_axes)``
    — the layout the sharded search's ``shard_map`` reads, so no batch
    reshards the index.  A no-op for leaves already placed so."""
    sharding = slot_sharding(mesh, shard_axes)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), sidx)


def shard_rows(vectors: np.ndarray, shard: int, per: int) -> tuple[np.ndarray, int]:
    """Rows of contiguous shard ``shard`` (capacity ``per``), padded to
    ``per`` by wrapping the shard's first row (or global row 0 when the shard
    is past the end of the data).  Returns ``(rows, n_real)``.

    This is the canonical shard input: ``build_sharded`` and the repair
    path's from-source rebuild both call it, so a repaired shard is built
    from bit-identical input."""
    vectors = np.asarray(vectors, np.float32)
    rows = vectors[shard * per : (shard + 1) * per]
    n_real = int(rows.shape[0])
    if n_real < per:  # pad by wrapping
        pad = np.tile(rows[:1] if rows.size else vectors[:1],
                      (per - n_real, 1))
        rows = np.concatenate([rows, pad]) if rows.size else pad
    return rows, n_real


def build_shard(rows: np.ndarray, shard: int,
                params: Optional[BuildParams] = None,
                quantized: bool = False, seed: int = 0):
    """Build one shard's index exactly as ``build_sharded`` would (per-shard
    seed derivation ``seed + shard``) — shared with ``core.repair`` so a
    rebuilt shard is bit-identical to the original."""
    p = dataclasses.replace(params or BuildParams(), seed=seed + shard)
    if quantized:
        return build_emqg(rows, p)
    return build_approx(rows, p)


def build_sharded(vectors, n_shards: int, params: Optional[BuildParams] = None,
                  quantized: bool = False, seed: int = 0) -> ShardedIndex:
    """Contiguous row partition; per-shard Algorithm-4 builds (equal-sized,
    last shard padded by wrapping).  Every shard is built on the default
    device, so all shards run the same compiled build programs; spread the
    result over a mesh with ``place_sharded``."""
    vectors = np.asarray(vectors, np.float32)
    n = vectors.shape[0]
    per = int(np.ceil(n / n_shards))
    shards, offsets, sizes = [], [], []
    for s in range(n_shards):
        rows, n_real = shard_rows(vectors, s, per)
        shards.append(build_shard(rows, s, params, quantized, seed))
        offsets.append(s * per)
        sizes.append(n_real)
    return stack_indices(shards, offsets, n, sizes=sizes)


def _local_search(index, queries, params: SearchParams, quantized: bool):
    if quantized:
        return probing_search(index, queries, params)
    return search(index, queries, params)


def _merge_all_gather(ids, dists, k, axis):
    """ids/dists [B, k] per shard → exact global top-k, replicated."""
    all_ids = jax.lax.all_gather(ids, axis, axis=1)      # [B, S, k]
    all_d = jax.lax.all_gather(dists, axis, axis=1)
    B = ids.shape[0]
    flat_i = all_ids.reshape(B, -1)
    flat_d = all_d.reshape(B, -1)
    neg, idx = jax.lax.top_k(-flat_d, k)
    return jnp.take_along_axis(flat_i, idx, axis=1), -neg


def _merge_ring(ids, dists, k, axis, n_shards):
    """(S−1)-step ppermute ring merge; ends replicated (each device has seen
    every shard's list exactly once)."""
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, _):
        cur_i, cur_d, acc_i, acc_d = carry
        cur_i = jax.lax.ppermute(cur_i, axis, perm)
        cur_d = jax.lax.ppermute(cur_d, axis, perm)
        cat_i = jnp.concatenate([acc_i, cur_i], axis=1)
        cat_d = jnp.concatenate([acc_d, cur_d], axis=1)
        neg, idx = jax.lax.top_k(-cat_d, k)
        return (cur_i, cur_d, jnp.take_along_axis(cat_i, idx, axis=1), -neg), None

    (_, _, acc_i, acc_d), _ = jax.lax.scan(
        step, (ids, dists, ids, dists), None, length=n_shards - 1)
    return acc_i, acc_d


def make_sharded_search(mesh, shard_axes=("data",), query_axis=None,
                        merge: str = "all_gather", quantized: bool = False):
    """Build a jit-able sharded search fn over ``mesh``.

    ``shard_axes``: mesh axes the index shards span (S = their product).
    ``query_axis``: mesh axis (or tuple) the query batch is sharded over
    (None → all queries on every device).  Sharding queries over the axes
    *not* used for index shards turns those axes into throughput parallelism
    — e.g. index over 'data', queries over ('pod','model').
    Returns fn(sharded_index, queries [B, d], params) → (ids, dists) [B, k]
    with outputs replicated over ``shard_axes`` and sharded over
    ``query_axis``; fn is jitted (``params`` static), so a batch of a shape
    already served runs without retracing.  The ring merge needs a single shard axis (ppermute is
    defined on one mesh axis); multi-axis shards use all_gather.
    """
    axis_name = shard_axes if len(shard_axes) > 1 else shard_axes[0]
    n_shards = int(np.prod([mesh.shape[a] for a in shard_axes]))
    if merge == "ring" and len(shard_axes) > 1:
        raise ValueError("ring merge requires a single shard axis")
    q_spec = P(query_axis) if query_axis else P()

    def body(sidx: ShardedIndex, queries, valid, params: SearchParams):
        local_index = jax.tree.map(lambda x: x[0], sidx.index)
        offset = sidx.offsets[0]
        res = _local_search(local_index, queries, params, quantized)
        # mask dead shards *before* the merge: their candidates become
        # (id=-1, dist=inf) and can never displace a live shard's entry —
        # both merge strategies then exclude them for free
        keep = valid[0] & (res.ids >= 0)
        if sidx.sizes is not None:
            # pad rows (local id >= sizes) are wrapped copies of the shard's
            # first row, whose real copy competes in the same local top-k —
            # mask them like dead-shard entries so no id >= n_total leaks
            # and no id appears twice in the merged top-k
            keep = keep & (res.ids < sidx.sizes[0])
        gids = jnp.where(keep, res.ids + offset, -1)
        d = jnp.where(gids >= 0, res.dists, jnp.inf)
        if merge == "ring":
            mi, md = _merge_ring(gids, d, params.k, axis_name, n_shards)
        else:
            mi, md = _merge_all_gather(gids, d, params.k, axis_name)
        return jnp.where(jnp.isfinite(md), mi, -1), md

    @partial(jax.jit, static_argnames=("params",))
    def run(sidx: ShardedIndex, queries, params: SearchParams, valid=None):
        if valid is None:
            valid = jnp.ones((n_shards,), bool)
        index_specs = jax.tree.map(lambda _: P(shard_axes), sidx.index)
        in_specs = (
            ShardedIndex(index=index_specs, offsets=P(shard_axes),
                         n_total=sidx.n_total,
                         sizes=None if sidx.sizes is None else P(shard_axes)),
            q_spec,
            P(shard_axes),
        )
        fn = jax.shard_map(
            partial(body, params=params),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(q_spec, q_spec),
            check_vma=False,
        )
        return fn(sidx, queries, jnp.asarray(valid, bool))

    return run


# ---------------------------------------------------------------------------
# Shard health + coverage accounting (module docstring, fault tolerance).
# ---------------------------------------------------------------------------

def build_replicated(vectors, n_shards: int, n_replicas: int = 2,
                     params: Optional[BuildParams] = None,
                     quantized: bool = False, seed: int = 0) -> ShardedIndex:
    """``build_sharded`` with each shard repeated R times — physical slot
    layout ``s·R + r`` (replicas of a shard are adjacent)."""
    base = build_sharded(vectors, n_shards, params, quantized, seed)
    if n_replicas == 1:
        return base
    index = jax.tree.map(lambda x: jnp.repeat(x, n_replicas, axis=0),
                         base.index)
    offsets = jnp.repeat(base.offsets, n_replicas)
    sizes = None if base.sizes is None else jnp.repeat(base.sizes, n_replicas)
    return ShardedIndex(index=index, offsets=offsets, n_total=base.n_total,
                        sizes=sizes)


class ShardHealthRegistry:
    """Host-side liveness over S logical shards × R replicas.

    ``participation()`` is the per-physical-slot mask handed to the sharded
    search: at most ONE live replica per logical shard participates (two
    replicas contributing the same rows would fill the merged top-k with
    duplicate ids).  A logical shard is covered iff any replica is live.

    Liveness can be driven two ways: explicitly (``mark_dead`` /
    ``mark_live`` — the operator surface, and what the fault harness's
    ``ShardDeathPlan`` calls) or implicitly via **heartbeats** — every
    replica records ``heartbeat()`` timestamps on the injectable monotonic
    ``clock``, and a :class:`DeadlineHealthChecker` auto-``mark_dead``s any
    live replica whose heartbeat age exceeds its deadline.  ``publish``
    mirrors the state into an ``obs`` registry (``shard_live{shard}``,
    ``shard_coverage``, ``shard_failover`` gauges).
    """

    def __init__(self, n_shards: int, n_replicas: int = 1,
                 clock=None):
        import time as _time
        self.n_shards = n_shards
        self.n_replicas = n_replicas
        self.clock = clock if clock is not None else _time.perf_counter
        self._live = np.ones((n_shards, n_replicas), bool)
        now = self.clock()
        self._last_beat = np.full((n_shards, n_replicas), now, float)

    def mark_dead(self, shard: int, replica: int = 0) -> None:
        self._live[shard, replica] = False

    def mark_live(self, shard: int, replica: int = 0) -> None:
        self._live[shard, replica] = True
        self._last_beat[shard, replica] = self.clock()

    def heartbeat(self, shard: int, replica: int = 0,
                  now: Optional[float] = None) -> None:
        """Record a liveness heartbeat for one replica (does NOT revive a
        slot already marked dead — a zombie's late beat must not undo an
        operator/checker kill; use ``mark_live`` for explicit revival)."""
        self._last_beat[shard, replica] = \
            now if now is not None else self.clock()

    def heartbeat_age(self, shard: int, replica: int = 0,
                      now: Optional[float] = None) -> float:
        now = now if now is not None else self.clock()
        return float(now - self._last_beat[shard, replica])

    def publish(self, metrics) -> None:
        """Mirror liveness into an ``obs.MetricsRegistry`` as gauges."""
        for s in range(self.n_shards):
            metrics.gauge("shard_live", {"shard": s}).set(
                float(self._live[s].any()))
        metrics.gauge("shard_coverage").set(self.coverage())
        metrics.gauge("shard_failover").set(self.n_failover)

    def live_shards(self) -> list[int]:
        return [s for s in range(self.n_shards) if self._live[s].any()]

    def dead_shards(self) -> list[int]:
        return [s for s in range(self.n_shards) if not self._live[s].any()]

    def coverage(self) -> float:
        return len(self.live_shards()) / self.n_shards

    @property
    def n_failover(self) -> int:
        """Logical shards currently served by a non-primary replica."""
        return int(sum(1 for s in range(self.n_shards)
                       if not self._live[s, 0] and self._live[s].any()))

    def participation(self) -> np.ndarray:
        """bool[S·R] — first live replica of each logical shard."""
        mask = np.zeros((self.n_shards, self.n_replicas), bool)
        for s in range(self.n_shards):
            alive = np.where(self._live[s])[0]
            if alive.size:
                mask[s, alive[0]] = True
        return mask.ravel()


class DeadlineHealthChecker:
    """Deadline-based shard health: a live replica whose last heartbeat is
    older than ``deadline_s`` is automatically ``mark_dead``-ed.

    This closes the loop the operator surface left open — ``kill_shard``
    required someone to *notice* the failure; the checker notices.  Call
    :meth:`check` from the serve loop (it is O(S·R) numpy reads — cheap per
    batch) or a timer.  Deterministically testable: both the registry clock
    and ``check(now=...)`` are injectable, so a fault schedule can age
    heartbeats without sleeping.

    With ``metrics``, every check refreshes two gauge families:
    ``shard_replica_heartbeat_age_seconds{shard,replica}`` — the raw
    heartbeat age of every slot, live or dead (what the deadline is compared
    against, per replica) — and the per-shard rollup
    ``shard_heartbeat_age_seconds{shard}``, which is the **min** age over the
    shard's *live* replicas (the freshest live replica; ``inf`` when every
    replica is dead — the shard-level "how stale is the healthiest copy"
    signal).  It also bumps ``shard_marked_dead_total`` per kill, emits a
    ``shard_deadline_expired`` structured event, and republishes the
    liveness gauges.
    """

    def __init__(self, registry: ShardHealthRegistry, deadline_s: float,
                 metrics=None):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.registry = registry
        self.deadline_s = float(deadline_s)
        self.metrics = metrics
        self.n_checks = 0
        self.n_killed = 0

    def check(self, now: Optional[float] = None) -> list[tuple[int, int]]:
        """One sweep; returns the (shard, replica) slots killed this call."""
        reg = self.registry
        now = now if now is not None else reg.clock()
        self.n_checks += 1
        killed: list[tuple[int, int]] = []
        for s in range(reg.n_shards):
            for r in range(reg.n_replicas):
                age = reg.heartbeat_age(s, r, now=now)
                if self.metrics is not None:
                    self.metrics.gauge(
                        "shard_replica_heartbeat_age_seconds",
                        {"shard": s, "replica": r}).set(age)
                if not reg._live[s, r]:
                    continue
                if age > self.deadline_s:
                    reg.mark_dead(s, r)
                    killed.append((s, r))
                    self.n_killed += 1
                    if self.metrics is not None:
                        self.metrics.counter("shard_marked_dead_total").inc()
                        self.metrics.event(
                            "shard_deadline_expired", shard=s, replica=r,
                            age_s=age, deadline_s=self.deadline_s)
            if self.metrics is not None:
                live = np.where(reg._live[s])[0]
                age_s = min((reg.heartbeat_age(s, r, now=now) for r in live),
                            default=math.inf)
                self.metrics.gauge("shard_heartbeat_age_seconds",
                                   {"shard": s}).set(age_s)
        if self.metrics is not None:
            reg.publish(self.metrics)
        return killed


@dataclasses.dataclass(frozen=True)
class ShardedSearchResult:
    """Merged top-k plus explicit per-response degradation accounting."""

    ids: jax.Array                 # [B, k] global ids (-1 where unfilled)
    dists: jax.Array               # [B, k]
    coverage: float                # live logical shards / S
    live_shards: int
    n_shards: int
    max_missed: int                # worst-case true neighbors lost to dead shards
    failover: int                  # shards answered by a non-primary replica


class FaultTolerantShardedSearch:
    """Host wrapper: registry-masked sharded search with coverage accounting.

    The mask is recomputed from the registry on every call, so marking a
    shard dead (or a replica live again) takes effect on the next query
    batch without re-tracing — ``valid`` is a runtime array input.
    """

    def __init__(self, sidx: ShardedIndex, mesh, shard_axes=("data",),
                 query_axis=None, merge: str = "all_gather",
                 quantized: bool = False, n_replicas: int = 1,
                 registry: Optional[ShardHealthRegistry] = None):
        n_slots = sidx.n_shards
        if n_slots % n_replicas:
            raise ValueError(f"{n_slots} slots not divisible by "
                             f"{n_replicas} replicas")
        self.sidx = place_sharded(sidx, mesh, shard_axes)
        self.quantized = quantized
        # a shared registry lets several searchers (e.g. the two merge
        # strategies of a resilient server) see one liveness truth
        self.registry = registry if registry is not None else \
            ShardHealthRegistry(n_slots // n_replicas, n_replicas)
        if self.registry.n_shards * self.registry.n_replicas != n_slots:
            raise ValueError("registry shape does not match index slots")
        self._run = make_sharded_search(mesh, shard_axes=shard_axes,
                                        query_axis=query_axis, merge=merge,
                                        quantized=quantized)
        if sidx.sizes is not None:
            self.shard_sizes = np.asarray(sidx.sizes)[::n_replicas].astype(int)
        else:
            offs = np.asarray(sidx.offsets)[::n_replicas]
            self.shard_sizes = np.diff(
                np.append(offs, sidx.n_total)).astype(int)

    def lower(self, queries, params: SearchParams):
        """``jax.stages.Lowered`` of the masked sharded search for this
        batch shape (compile it to check the program without running)."""
        return self._run.lower(self.sidx, queries, params,
                               valid=self.registry.participation())

    def __call__(self, queries, params: SearchParams) -> ShardedSearchResult:
        mask = self.registry.participation()
        if not mask.any():
            raise RuntimeError("no live shard replicas")
        ids, dists = self._run(self.sidx, queries, params, valid=mask)
        dead = self.registry.dead_shards()
        max_missed = int(min(params.k,
                             sum(min(params.k, self.shard_sizes[s])
                                 for s in dead)))
        return ShardedSearchResult(
            ids=ids, dists=dists,
            coverage=self.registry.coverage(),
            live_shards=len(self.registry.live_shards()),
            n_shards=self.registry.n_shards,
            max_missed=max_missed,
            failover=self.registry.n_failover)


def host_reference_merge(sidx: ShardedIndex, registry: ShardHealthRegistry,
                         queries, params: SearchParams,
                         quantized: bool = False):
    """Oracle for the masked merge: per-slot searches on the host, merged
    over exactly the participating slots.  O(S) sequential searches — test
    and audit use only."""
    mask = registry.participation()
    all_i, all_d = [], []
    dev = jax.devices()[0]          # one device: one compile for every slot
    for slot in np.where(mask)[0]:
        local = jax.tree.map(lambda x, s=slot: jax.device_put(x[s], dev),
                             sidx.index)
        res = _local_search(local, queries, params, quantized)
        ids = np.asarray(res.ids)
        offs = int(np.asarray(sidx.offsets)[slot])
        keep = ids >= 0
        if sidx.sizes is not None:
            keep &= ids < int(np.asarray(sidx.sizes)[slot])
        all_i.append(np.where(keep, ids + offs, -1))
        all_d.append(np.where(keep, np.asarray(res.dists), np.inf))
    cat_i = np.concatenate(all_i, axis=1)
    cat_d = np.concatenate(all_d, axis=1)
    order = np.argsort(cat_d, axis=1, kind="stable")[:, : params.k]
    mi = np.take_along_axis(cat_i, order, axis=1)
    md = np.take_along_axis(cat_d, order, axis=1)
    return np.where(np.isfinite(md), mi, -1), md
