"""Streaming index maintenance: insert / delete on a live δ-EMG
(FreshDiskANN-style), without full rebuilds.

Insert (batched): search the current graph for each new point's
neighborhood (the same candidate generation as Algorithm 4), prune with the
adaptive occlusion rule, splice the new rows into the fixed-width adjacency,
and add reverse edges under the degree cap.  The δ-EMG closure is restored
*locally* — exactly the per-node operation one refinement iteration of
Algorithm 4 performs, so quality matches a rebuilt graph up to the usual
approximate-construction gap (tested).

Delete (lazy + consolidate): deletions mark a tombstone bitmap consulted by
``search_live`` (results filter tombstones; traversal still routes through
them, preserving connectivity — the FreshDiskANN insight).  When tombstones
exceed ``consolidate_frac``, ``consolidate`` splices each deleted node out
by locally reconnecting its in-neighbors to its out-neighbors under the
occlusion rule, then compacts.

Crash safety (``JournaledLiveIndex``): every mutation batch is journaled to
a write-ahead log *before* it touches the in-memory ``LiveIndex``.  A WAL
record is two files committed in order — ``wal_XXXXXXXXX.npz`` (payload
arrays) then ``wal_XXXXXXXXX.json`` (manifest: seq, op, per-array CRC32,
the same integrity conventions as ``checkpoint/manager.py``) — each written
via tmp + ``os.replace``.  A record is committed iff its manifest exists,
parses, and every checksum matches; a crash mid-append leaves a torn
(manifest-less or checksum-failing) record that recovery treats as
never-written.  Periodic full checkpoints (``checkpoint()``) bound replay
length; ``recover()`` restores the newest intact checkpoint (corrupt steps
are walked back, courtesy of the manager) and replays committed WAL
records in sequence.  Because every op is a deterministic function of
(state, payload), recovery reproduces the uninterrupted run bit-for-bit.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import time
import zlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import list_steps, restore_latest, save_checkpoint

from .build_approx import (BuildParams, _prep_candidates,
                           _repair_connectivity, _select_block)
from .distances import medoid as find_medoid
from .search import SearchParams, search
from .types import GraphIndex, SearchResult

log = logging.getLogger("repro.updates")


@dataclasses.dataclass
class LiveIndex:
    """A δ-EMG plus mutation state (host-managed, device-resident arrays)."""

    graph: GraphIndex
    tombstones: np.ndarray            # bool[n]
    params: BuildParams

    @property
    def n_live(self) -> int:
        return int((~self.tombstones).sum())

    @property
    def frac_deleted(self) -> float:
        return float(self.tombstones.mean())


def as_live(graph: GraphIndex, params: Optional[BuildParams] = None) -> LiveIndex:
    return LiveIndex(graph=graph,
                     tombstones=np.zeros(graph.n, bool),
                     params=params or BuildParams())


def insert(live: LiveIndex, new_vectors: np.ndarray,
           fault_hook: Optional[Callable[[str], None]] = None) -> LiveIndex:
    """Batched insertion.  Returns a new LiveIndex (functional host state).

    ``fault_hook`` (testing only) is called at the ``mid_splice`` point —
    after the new rows are spliced into the adjacency but before reverse
    edges restore the local δ-closure; a hook that raises simulates a crash
    that leaves a half-mutated adjacency on the floor."""
    p = live.params
    g = live.graph
    vec_np = np.asarray(g.vectors)
    new_vectors = np.asarray(new_vectors, np.float32)
    m = new_vectors.shape[0]
    n0 = g.n
    M = g.max_degree
    L = min(p.beam_width, n0)

    # candidate generation on the current graph
    sp = SearchParams(k=min(L, n0), l0=L, l_max=L, adaptive=False,
                      max_hops=p.max_hops)
    _, cand_ids, cand_dists = search(g, jnp.asarray(new_vectors), sp,
                                     with_candidates=True)

    all_vecs = np.concatenate([vec_np, new_vectors])
    vectors = jnp.asarray(all_vecs)
    new_ids = jnp.arange(n0, n0 + m, dtype=jnp.int32)
    kept, cnt = _select_block(
        vectors, new_ids, cand_ids, cand_dists,
        t=min(p.t, L), rule=p.rule, max_keep=M, fixed_delta=p.delta)
    kept, cnt = np.array(kept), np.array(cnt)

    nbr = np.concatenate([np.asarray(g.neighbors),
                          np.full((m, M), -1, np.int32)])
    deg = (nbr >= 0).sum(1).astype(np.int32)
    nbr[n0:] = kept
    deg[n0:] = cnt
    if fault_hook is not None:
        fault_hook("mid_splice")

    # reverse edges under the cap; replace the longest edge when full so new
    # nodes always become reachable (same rule as connectivity repair)
    for j in range(m):
        u = n0 + j
        for v in kept[j, : cnt[j]].tolist():
            row = nbr[v, : deg[v]]
            if (row == u).any():
                continue
            if deg[v] < M:
                nbr[v, deg[v]] = u
                deg[v] += 1
            else:
                d2row = ((all_vecs[nbr[v, :M]] - all_vecs[v]) ** 2).sum(-1)
                worst = int(np.argmax(d2row))
                if d2row[worst] > ((all_vecs[u] - all_vecs[v]) ** 2).sum():
                    nbr[v, worst] = u

    # evicting a full row's longest edge above can sever some node's only
    # in-edge — run the builder's connectivity repair so every node stays
    # reachable from the medoid (deterministic, so WAL replay reproduces it)
    deg = (nbr >= 0).sum(1).astype(np.int32)
    _repair_connectivity(all_vecs, nbr, deg, M, int(np.asarray(g.medoid)))

    graph = GraphIndex(vectors=vectors, neighbors=jnp.asarray(nbr),
                       medoid=g.medoid, kind=g.kind, delta=g.delta)
    tomb = np.concatenate([live.tombstones, np.zeros(m, bool)])
    return LiveIndex(graph=graph, tombstones=tomb, params=p)


def delete(live: LiveIndex, ids) -> LiveIndex:
    tomb = live.tombstones.copy()
    tomb[np.asarray(ids)] = True
    return LiveIndex(graph=live.graph, tombstones=tomb, params=live.params)


def search_live(live: LiveIndex, queries, k: int, alpha: float = 1.2,
                l_max: int = 128, **kw) -> SearchResult:
    """Error-bounded search that filters tombstones from the results while
    still routing through them.  Over-fetches k + #tombstone-margin."""
    over = int(min(l_max, k + max(8, 4 * int(live.tombstones.sum() > 0) * k)))
    p = SearchParams(k=over, l0=over, l_max=l_max, alpha=alpha,
                     adaptive=True, max_hops=kw.pop("max_hops", 2048))
    res = search(live.graph, jnp.asarray(queries), p, **kw)
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    out_ids = np.full((ids.shape[0], k), -1, np.int32)
    out_d = np.full((ids.shape[0], k), np.inf, np.float32)
    for b in range(ids.shape[0]):
        keep = [(d, i) for d, i in zip(dists[b], ids[b])
                if i >= 0 and not live.tombstones[i]][:k]
        for j, (d, i) in enumerate(keep):
            out_ids[b, j] = i
            out_d[b, j] = d
    return dataclasses.replace(res, ids=jnp.asarray(out_ids),
                               dists=jnp.asarray(out_d))


def consolidate(live: LiveIndex) -> LiveIndex:
    """Splice tombstoned nodes out: reconnect in-neighbors to the deleted
    node's out-neighbors (occlusion-pruned), then compact ids."""
    p = live.params
    g = live.graph
    vec_np = np.asarray(g.vectors)
    nbr = np.asarray(g.neighbors).copy()
    tomb = live.tombstones
    n, M = nbr.shape
    dead = set(np.where(tomb)[0].tolist())
    if not dead:
        return live

    # in-neighbor lists of dead nodes
    in_of_dead: dict[int, list[int]] = {d: [] for d in dead}
    for u in range(n):
        if u in dead:
            continue
        for v in nbr[u]:
            if v >= 0 and int(v) in dead:
                in_of_dead[int(v)].append(u)

    vectors = g.vectors
    touched = set()
    for d, in_nbrs in in_of_dead.items():
        repl = [int(x) for x in nbr[d] if x >= 0 and int(x) not in dead]
        for u in in_nbrs:
            row = [int(x) for x in nbr[u] if x >= 0 and int(x) not in dead]
            merged = np.asarray(sorted(set(row + repl) - {u}), np.int64)
            if merged.size == 0:
                continue
            ids = jnp.asarray(np.pad(merged, (0, max(0, 2 * M - merged.size)),
                                     constant_values=-1)[: 2 * M].astype(np.int32))
            d2 = np.linalg.norm(vec_np[np.maximum(np.asarray(ids), 0)]
                                - vec_np[u], axis=1)
            cand_ids, cand_dists = _prep_candidates(
                vectors, jnp.asarray([u], jnp.int32), ids[None], 2 * M - 1)
            kept, cnt = _select_block(
                vectors, jnp.asarray([u], jnp.int32), cand_ids, cand_dists,
                t=min(p.t, 2 * M - 1), rule=p.rule, max_keep=M,
                fixed_delta=p.delta)
            nbr[u] = np.array(kept)[0]
            touched.add(u)

    # compact: drop dead rows, remap ids
    alive = np.where(~tomb)[0]
    remap = -np.ones(n, np.int64)
    remap[alive] = np.arange(alive.size)
    new_nbr = nbr[alive]
    valid = new_nbr >= 0
    new_nbr = np.where(valid, remap[np.maximum(new_nbr, 0)], -1).astype(np.int32)
    new_nbr[new_nbr == -1] = -1
    new_vec = vec_np[alive]
    med = find_medoid(new_vec)
    graph = GraphIndex(vectors=jnp.asarray(new_vec),
                       neighbors=jnp.asarray(new_nbr),
                       medoid=jnp.int32(med), kind=g.kind, delta=g.delta)
    return LiveIndex(graph=graph, tombstones=np.zeros(alive.size, bool),
                     params=p)


# ---------------------------------------------------------------------------
# Write-ahead log + crash-safe journaled index (module docstring, part 2).
# ---------------------------------------------------------------------------

_WAL_RE = re.compile(r"^wal_(\d{9})\.json$")


class WalCorruptError(RuntimeError):
    """A WAL record failed integrity checks (treated as never-written)."""


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _atomic_write(path: str, data: bytes,
                  fsync_hist=None) -> None:
    """tmp + fsync + rename.  ``fsync_hist`` (an ``obs.Histogram``) times
    the fsync alone — on real disks that is where WAL commit latency lives,
    and it is the number a "why did p99 spike" investigation needs split
    from serialization cost."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        if fsync_hist is not None:
            t0 = time.perf_counter()
            os.fsync(f.fileno())
            fsync_hist.observe(time.perf_counter() - t0)
        else:
            os.fsync(f.fileno())
    os.replace(tmp, path)


def wal_append(wal_dir: str, seq: int, op: str,
               payload: dict[str, np.ndarray],
               fault_hook: Optional[Callable[[str], None]] = None,
               metrics=None, compress: bool = False) -> str:
    """Append one committed record.  Payload npz lands first, the manifest
    (whose existence *is* the commit) second — a crash between the two
    (the ``torn_journal`` fault point) leaves an uncommitted torn record.

    ``compress`` writes the payload with ``np.savez_compressed`` — the
    manifest checksums the *arrays*, not the file, so compressed and plain
    records verify and replay identically (``wal_read`` is format-blind).

    ``metrics`` (an ``obs.MetricsRegistry``) times the whole append into
    ``wal_append_seconds``, each fsync into ``wal_fsync_seconds``, and
    counts ``wal_records_total{op}``."""
    t_start = time.perf_counter()
    fsync_hist = None if metrics is None else \
        metrics.histogram("wal_fsync_seconds")
    os.makedirs(wal_dir, exist_ok=True)
    base = os.path.join(wal_dir, f"wal_{seq:09d}")
    import io
    buf = io.BytesIO()
    (np.savez_compressed if compress else np.savez)(buf, **payload)
    _atomic_write(base + ".npz", buf.getvalue(), fsync_hist=fsync_hist)
    if fault_hook is not None:
        fault_hook("torn_journal")
    manifest = {
        "seq": seq,
        "op": op,
        "keys": sorted(payload.keys()),
        "dtypes": {k: str(v.dtype) for k, v in payload.items()},
        "shapes": {k: list(v.shape) for k, v in payload.items()},
        "checksums": {k: _crc(v) for k, v in payload.items()},
    }
    _atomic_write(base + ".json", json.dumps(manifest).encode(),
                  fsync_hist=fsync_hist)
    if metrics is not None:
        metrics.histogram("wal_append_seconds").observe(
            time.perf_counter() - t_start)
        metrics.counter("wal_records_total", {"op": op}).inc()
    return base + ".json"


def wal_read(wal_dir: str, seq: int) -> tuple[str, dict[str, np.ndarray]]:
    """Load + verify one record.  Raises ``WalCorruptError`` on any
    integrity violation (missing/torn manifest, unreadable npz, checksum
    mismatch) — recovery treats those records as never-written."""
    base = os.path.join(wal_dir, f"wal_{seq:09d}")
    if not os.path.exists(base + ".json") and not os.path.exists(base + ".npz"):
        raise FileNotFoundError(f"no WAL record {seq}")   # clean end of log
    try:
        with open(base + ".json") as f:
            manifest = json.load(f)
    except Exception as e:
        # payload present but manifest missing/unparsable: torn record
        raise WalCorruptError(f"record {seq}: unreadable manifest: {e}") from e
    try:
        with np.load(base + ".npz") as z:
            payload = {k: z[k].copy() for k in z.files}
    except Exception as e:
        raise WalCorruptError(f"record {seq}: unreadable payload: {e}") from e
    if set(manifest.get("keys", [])) != set(payload.keys()):
        raise WalCorruptError(f"record {seq}: manifest/payload key mismatch")
    for k, arr in payload.items():
        want = manifest["checksums"].get(k)
        if want is not None and _crc(arr) != want:
            raise WalCorruptError(f"record {seq}: checksum mismatch on {k!r}")
    return manifest["op"], payload


def wal_seqs(wal_dir: str) -> list[int]:
    """Sequence numbers of records with a manifest present (not verified)."""
    if not os.path.isdir(wal_dir):
        return []
    return sorted(int(m.group(1))
                  for m in map(_WAL_RE.match, os.listdir(wal_dir)) if m)


def _record_bytes(wal_dir: str, seq: int) -> int:
    """On-disk footprint of one record (payload + manifest; 0 if absent)."""
    base = os.path.join(wal_dir, f"wal_{seq:09d}")
    total = 0
    for suffix in (".npz", ".json"):
        try:
            total += os.path.getsize(base + suffix)
        except OSError:
            pass
    return total


def _truncate_wal(wal_dir: str, upto_seq: int) -> None:
    for s in wal_seqs(wal_dir):
        if s <= upto_seq:
            base = os.path.join(wal_dir, f"wal_{s:09d}")
            for suffix in (".json", ".npz"):
                try:
                    os.remove(base + suffix)
                except FileNotFoundError:
                    pass


def _apply_op(live: LiveIndex, op: str, payload: dict,
              fault_hook=None) -> LiveIndex:
    """Deterministic op application — shared by the live path and replay."""
    if op == "insert":
        return insert(live, payload["vectors"], fault_hook=fault_hook)
    if op == "delete":
        return delete(live, payload["ids"])
    if op == "consolidate":
        return consolidate(live)
    raise ValueError(f"unknown WAL op: {op!r}")


class JournaledLiveIndex:
    """A ``LiveIndex`` whose mutations are crash-safe (WAL + checkpoints).

    Layout under ``directory``::

        meta.json            static state (BuildParams, kind, δ) — written once
        ckpt/step_XXXXXXXXX/ full snapshots via ``checkpoint.manager``
                             (step number == WAL sequence at save time)
        wal/wal_XXXXXXXXX.{npz,json}   journal records (seq 1, 2, ...)

    ``fault_hook(point)`` (testing only) is invoked at the named crash
    points — ``before_journal``, ``torn_journal``, ``after_journal``,
    ``mid_splice`` — with the convention that a raising hook simulates the
    process dying there; the on-disk state is whatever the protocol had
    durably committed by that point.

    ``consolidate_frac``: when a delete pushes the tombstone fraction past
    this threshold, a ``consolidate`` is triggered automatically — and
    journaled as its own record, so replay re-runs it at the same position
    in the op stream.

    ``checkpoint_every_bytes``: when the WAL grows past this many bytes
    since the last checkpoint (measured as on-disk record footprint — the
    quantity that actually bounds recovery replay I/O, unlike an op count,
    which a single large insert batch defeats), a checkpoint is taken
    automatically right after the mutation commits.  ``compress`` writes
    WAL payloads with ``np.savez_compressed``; both knobs are persisted in
    ``meta.json`` so ``recover()`` restores them (and the byte accumulator)
    and stays bit-identical either way.
    """

    def __init__(self, live: LiveIndex, directory: str, *,
                 seq: int = 0, consolidate_frac: float = 0.3,
                 keep_checkpoints: int = 3,
                 checkpoint_every_bytes: Optional[int] = None,
                 compress: bool = False,
                 fault_hook: Optional[Callable[[str], None]] = None,
                 metrics=None):
        self.live = live
        self.directory = directory
        self.seq = seq
        self.consolidate_frac = consolidate_frac
        self.keep_checkpoints = keep_checkpoints
        self.checkpoint_every_bytes = checkpoint_every_bytes
        self.compress = compress
        self.fault_hook = fault_hook
        # obs.MetricsRegistry (or None): WAL append/fsync + checkpoint
        # save/restore timings, wal_records_total{op} — purely additive,
        # recovery semantics are identical with metrics on or off
        self.metrics = metrics
        self.wal_dir = os.path.join(directory, "wal")
        self.ckpt_dir = os.path.join(directory, "ckpt")
        self._wal_bytes = 0     # on-disk record bytes since last checkpoint

    # -- construction --------------------------------------------------------
    @classmethod
    def create(cls, live: LiveIndex, directory: str,
               **kw) -> "JournaledLiveIndex":
        """Initialize a journal directory: meta + a seq-0 base checkpoint."""
        os.makedirs(directory, exist_ok=True)
        self = cls(live, directory, **kw)
        meta = {
            "kind": live.graph.kind,
            "delta": live.graph.delta,
            "params": dataclasses.asdict(live.params),
            "consolidate_frac": self.consolidate_frac,
            "checkpoint_every_bytes": self.checkpoint_every_bytes,
            "compress": self.compress,
        }
        _atomic_write(os.path.join(directory, "meta.json"),
                      json.dumps(meta).encode())
        self.checkpoint()
        return self

    # -- state snapshot ------------------------------------------------------
    def _tree(self) -> dict[str, np.ndarray]:
        g = self.live.graph
        return {
            "vectors": np.asarray(g.vectors),
            "neighbors": np.asarray(g.neighbors),
            "medoid": np.asarray(g.medoid),
            "tombstones": np.asarray(self.live.tombstones),
        }

    def checkpoint(self) -> str:
        """Commit a full snapshot at the current sequence, then drop WAL
        records no retained checkpoint still needs (older snapshots kept by
        ``keep_checkpoints`` must stay replayable — if the newest snapshot
        is later found corrupt, recovery walks back and rolls forward)."""
        t0 = time.perf_counter()
        path = save_checkpoint(self.ckpt_dir, self.seq, self._tree(),
                               keep=self.keep_checkpoints)
        if self.metrics is not None:
            self.metrics.histogram("checkpoint_save_seconds").observe(
                time.perf_counter() - t0)
        steps = list_steps(self.ckpt_dir)
        if steps:
            _truncate_wal(self.wal_dir, min(steps))
        self._wal_bytes = 0
        return path

    # -- mutations (journal first, splice second) ----------------------------
    def _fault(self, point: str) -> None:
        if self.fault_hook is not None:
            self.fault_hook(point)

    def _mutate(self, op: str, payload: dict[str, np.ndarray]) -> None:
        self._fault("before_journal")
        wal_append(self.wal_dir, self.seq + 1, op, payload,
                   fault_hook=self.fault_hook, metrics=self.metrics,
                   compress=self.compress)
        self._fault("after_journal")
        self.live = _apply_op(self.live, op, payload,
                              fault_hook=self.fault_hook)
        self.seq += 1
        self._wal_bytes += _record_bytes(self.wal_dir, self.seq)
        if self.metrics is not None:
            self.metrics.gauge("wal_bytes_since_checkpoint").set(
                self._wal_bytes)
        if (self.checkpoint_every_bytes is not None
                and self._wal_bytes >= self.checkpoint_every_bytes):
            if self.metrics is not None:
                self.metrics.counter("wal_auto_checkpoint_total").inc()
                self.metrics.gauge("wal_bytes_since_checkpoint").set(0)
            self.checkpoint()

    def insert(self, vectors) -> None:
        self._mutate("insert",
                     {"vectors": np.asarray(vectors, np.float32)})

    def delete(self, ids) -> None:
        self._mutate("delete", {"ids": np.asarray(ids, np.int64)})
        if self.live.frac_deleted > self.consolidate_frac:
            self.consolidate()

    def consolidate(self) -> None:
        self._mutate("consolidate", {})

    def search(self, queries, k: int, **kw) -> SearchResult:
        return search_live(self.live, queries, k, **kw)

    @property
    def n_live(self) -> int:
        return self.live.n_live


def recover(directory: str, metrics=None) -> tuple[JournaledLiveIndex, dict]:
    """Rebuild a ``JournaledLiveIndex`` from disk after a crash.

    Restores the newest intact checkpoint (corrupt steps walk back inside
    ``restore_latest``), then replays committed WAL records in sequence; the
    replay stops at the first missing or torn record (= the op the crash
    interrupted before its commit point — by WAL semantics it never
    happened).  Returns ``(journal, info)`` where ``info`` reports the
    checkpoint step used, the records replayed, any torn record seen, and
    the restore wall time (``elapsed_s`` — also observed into
    ``checkpoint_restore_seconds`` when ``metrics`` is given; the returned
    journal keeps the registry for its own WAL/checkpoint timings).
    """
    t_start = time.perf_counter()
    with open(os.path.join(directory, "meta.json")) as f:
        meta = json.load(f)
    params = BuildParams(**meta["params"])
    template = {
        "vectors": np.zeros((0, 0), np.float32),
        "neighbors": np.zeros((0, 0), np.int32),
        "medoid": np.zeros((), np.int32),
        "tombstones": np.zeros((0,), np.bool_),
    }
    ckpt_dir = os.path.join(directory, "ckpt")
    wal_dir = os.path.join(directory, "wal")
    step, tree = restore_latest(ckpt_dir, template)
    if step is None:
        raise FileNotFoundError(
            f"no intact checkpoint under {ckpt_dir}; cannot recover")
    graph = GraphIndex(vectors=jnp.asarray(tree["vectors"]),
                       neighbors=jnp.asarray(tree["neighbors"]),
                       medoid=jnp.asarray(tree["medoid"], jnp.int32),
                       kind=meta["kind"], delta=meta["delta"])
    live = LiveIndex(graph=graph,
                     tombstones=np.asarray(tree["tombstones"], bool),
                     params=params)
    info = {"checkpoint_step": step, "replayed": 0, "torn_seq": None}
    seq = step
    while True:
        try:
            op, payload = wal_read(wal_dir, seq + 1)
        except WalCorruptError as e:
            # torn record: crash mid-append → op never committed
            log.warning("WAL replay stops at %s", e)
            info["torn_seq"] = seq + 1
            break
        except FileNotFoundError:
            break
        live = _apply_op(live, op, payload)
        seq += 1
        info["replayed"] += 1
    info["elapsed_s"] = time.perf_counter() - t_start
    if metrics is not None:
        metrics.histogram("checkpoint_restore_seconds").observe(
            info["elapsed_s"])
    journal = JournaledLiveIndex(
        live, directory, seq=seq,
        consolidate_frac=meta.get("consolidate_frac", 0.3),
        checkpoint_every_bytes=meta.get("checkpoint_every_bytes"),
        compress=meta.get("compress", False), metrics=metrics)
    # resume the byte accumulator: committed records newer than the restored
    # checkpoint are exactly what the next auto-checkpoint threshold is over
    journal._wal_bytes = sum(_record_bytes(wal_dir, s)
                             for s in range(step + 1, seq + 1))
    return journal, info
