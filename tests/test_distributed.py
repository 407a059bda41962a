"""Multi-device tests (sharded index search, merge exactness, shard loss).

These spawn subprocesses because --xla_force_host_platform_device_count must
be set before jax initializes, and the main pytest process must keep seeing
a single device for the smoke tests."""

import subprocess
import sys

import pytest

_PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import sys; sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
"""


def _run(body: str, n_devices: int = 8, timeout: int = 560) -> str:
    code = _PREAMBLE.format(n=n_devices) + body
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, cwd="/root/repo")
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_sharded_search_matches_brute_force():
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import build_sharded, make_sharded_search
from repro.core.distances import brute_force_knn
rng = np.random.default_rng(0)
X = rng.normal(size=(1024, 24)).astype(np.float32)
Q = rng.normal(size=(16, 24)).astype(np.float32)
gt_d, gt_i = brute_force_knn(Q, X, 10)
mesh = jax.make_mesh((4, 2), ("data", "model"))
sidx = build_sharded(X, 4, BuildParams(max_degree=16, beam_width=48, t=16, iters=2, block=512))
params = SearchParams(k=10, l0=10, l_max=64, alpha=2.0, adaptive=True, max_hops=512)
for merge in ("all_gather", "ring"):
    run = make_sharded_search(mesh, shard_axes=("data",), query_axis=None, merge=merge)
    ids, dists = run(sidx, jnp.asarray(Q), params)
    ids = np.asarray(ids)
    rec = np.mean([len(set(ids[i].tolist()) & set(gt_i[i].tolist()))/10 for i in range(16)])
    print(merge, "recall", rec)
    assert rec > 0.9, (merge, rec)
    d = np.asarray(dists)
    assert (np.diff(d, axis=1) >= -1e-5).all()
print("OK")
""")
    assert "OK" in out


def test_merge_strategies_agree():
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import build_sharded, make_sharded_search
rng = np.random.default_rng(1)
X = rng.normal(size=(512, 16)).astype(np.float32)
Q = rng.normal(size=(8, 16)).astype(np.float32)
mesh = jax.make_mesh((4, 2), ("data", "model"))
sidx = build_sharded(X, 4, BuildParams(max_degree=12, beam_width=24, t=8, iters=1, block=512))
params = SearchParams(k=5, l0=8, l_max=32, adaptive=False, max_hops=256)
runs = {m: make_sharded_search(mesh, shard_axes=("data",), query_axis=None, merge=m)
        for m in ("all_gather", "ring")}
outs = {m: np.asarray(r(sidx, jnp.asarray(Q), params)[0]) for m, r in runs.items()}
assert (outs["all_gather"] == outs["ring"]).all()
print("OK")
""")
    assert "OK" in out


def test_quantized_sharded_search():
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import build_sharded, make_sharded_search
from repro.core.distances import brute_force_knn
rng = np.random.default_rng(2)
X = rng.normal(size=(1024, 32)).astype(np.float32)
Q = rng.normal(size=(8, 32)).astype(np.float32)
gt_d, gt_i = brute_force_knn(Q, X, 10)
mesh = jax.make_mesh((4, 2), ("data", "model"))
sidx = build_sharded(X, 4, BuildParams(max_degree=16, beam_width=48, t=16, iters=2,
                                       block=512, align_degree=True), quantized=True)
params = SearchParams(k=10, l0=10, l_max=64, alpha=1.5, adaptive=True, max_hops=512)
run = make_sharded_search(mesh, shard_axes=("data",), query_axis=None,
                          merge="all_gather", quantized=True)
ids, dists = run(sidx, jnp.asarray(Q), params)
ids = np.asarray(ids)
rec = np.mean([len(set(ids[i].tolist()) & set(gt_i[i].tolist()))/10 for i in range(8)])
print("quantized recall", rec)
assert rec > 0.8
print("OK")
""")
    assert "OK" in out


def test_pad_rows_never_leak_global_ids():
    """Regression: the last shard's pad rows (wrapped copies of its first
    row) used to get global ids ``lo+j >= n_total``.  With the query sitting
    exactly ON the pad-source row the pads tie it at distance 0, so pre-fix
    they reached the merged top-k.  Both device merges and the host
    reference must now mask pads out like dead-shard entries: every
    returned id is in [0, n_total), valid ids are unique per row, and the
    pad-source row itself (whose real copy competes in the same local
    top-k) is still returned."""
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import (build_sharded, make_sharded_search,
                                    host_reference_merge, ShardHealthRegistry)
rng = np.random.default_rng(5)
X = rng.normal(size=(509, 16)).astype(np.float32)   # 4 shards of 128: 3 pads
sidx = build_sharded(X, 4, BuildParams(max_degree=12, beam_width=24, t=8,
                                       iters=1, block=512))
assert np.asarray(sidx.sizes).tolist() == [128, 128, 128, 125]
mesh = jax.make_mesh((4, 2), ("data", "model"))
params = SearchParams(k=8, l0=16, l_max=32, adaptive=False, max_hops=256)
# queries ON and near the pad-source row (global id 384 = last shard row 0)
Q = np.concatenate([X[384:385], X[384:385] + 0.01 * rng.normal(size=(3, 16)).astype(np.float32)])
def check(ids):
    ids = np.asarray(ids)
    assert ids.max() < sidx.n_total, ids.max()
    for row in ids:
        valid = row[row >= 0]
        assert len(set(valid.tolist())) == len(valid), row
    assert (ids[0] == 384).any()      # the source row itself is returned
for merge in ("all_gather", "ring"):
    run = make_sharded_search(mesh, shard_axes=("data",), merge=merge)
    ids, dists = run(sidx, jnp.asarray(Q), params)
    check(ids)
ref_i, _ = host_reference_merge(sidx, ShardHealthRegistry(4), jnp.asarray(Q),
                                params)
check(ref_i)
print("OK")
""")
    assert "OK" in out


def test_query_axis_sharding():
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import build_sharded, make_sharded_search
rng = np.random.default_rng(3)
X = rng.normal(size=(512, 16)).astype(np.float32)
Q = rng.normal(size=(8, 16)).astype(np.float32)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
sidx = build_sharded(X, 2, BuildParams(max_degree=12, beam_width=24, t=8, iters=1, block=512))
params = SearchParams(k=5, l0=8, l_max=32, adaptive=False, max_hops=256)
run = make_sharded_search(mesh, shard_axes=("data",), query_axis=("pod", "model"))
ids, dists = run(sidx, jnp.asarray(Q), params)
assert ids.shape == (8, 5)
run2 = make_sharded_search(mesh, shard_axes=("data",), query_axis=None)
ids2, _ = run2(sidx, jnp.asarray(Q), params)
assert (np.asarray(ids) == np.asarray(ids2)).all()
print("OK")
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Shard-loss tolerance: masked merges, coverage accounting, replica failover.
# ---------------------------------------------------------------------------


@pytest.mark.faults
def test_dead_shard_masked_merge_matches_survivor_reference():
    """With 1 of S shards killed the response must carry coverage=(S-1)/S
    and the merged ids must exactly equal the reference merge over the
    surviving shards — for BOTH merge strategies — with no dead-shard id
    leaking through."""
    out = _run("""
import os
from repro.core import BuildParams, SearchParams
from repro.core.distributed import (build_sharded, FaultTolerantShardedSearch,
                                    host_reference_merge)
seed = int(os.environ.get("REPRO_FAULT_SEED", "0"))
rng = np.random.default_rng(seed)
X = rng.normal(size=(512, 16)).astype(np.float32)
Q = rng.normal(size=(8, 16)).astype(np.float32)
mesh = jax.make_mesh((4, 2), ("data", "model"))
sidx = build_sharded(X, 4, BuildParams(max_degree=12, beam_width=24, t=8,
                                       iters=1, block=512))
params = SearchParams(k=5, l0=8, l_max=32, adaptive=False, max_hops=256)
dead = int(rng.integers(0, 4))
offs = np.append(np.asarray(sidx.offsets), sidx.n_total)
for merge in ("all_gather", "ring"):
    fts = FaultTolerantShardedSearch(sidx, mesh, merge=merge)
    fts.registry.mark_dead(dead)
    r = fts(jnp.asarray(Q), params)
    assert abs(r.coverage - 3/4) < 1e-9, r.coverage
    assert r.live_shards == 3 and r.n_shards == 4
    assert r.max_missed == min(params.k, int(offs[dead+1] - offs[dead]))
    ids = np.asarray(r.ids)
    assert not (((ids >= offs[dead]) & (ids < offs[dead+1])).any())
    ref_i, ref_d = host_reference_merge(sidx, fts.registry, jnp.asarray(Q),
                                        params)
    assert (ids == ref_i).all(), (merge, ids[0], ref_i[0])
    np.testing.assert_allclose(np.asarray(r.dists), ref_d, rtol=1e-6)
print("OK")
""")
    assert "OK" in out


@pytest.mark.faults
def test_replica_failover_restores_full_coverage():
    """Losing a primary with a live replica must fail over (coverage stays
    1.0, identical results); losing both degrades coverage; reviving
    restores it."""
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import build_replicated, FaultTolerantShardedSearch
rng = np.random.default_rng(4)
X = rng.normal(size=(512, 16)).astype(np.float32)
Q = rng.normal(size=(8, 16)).astype(np.float32)
mesh = jax.make_mesh((8,), ("data",))
sidx = build_replicated(X, 4, 2, BuildParams(max_degree=12, beam_width=24,
                                             t=8, iters=1, block=512))
params = SearchParams(k=5, l0=8, l_max=32, adaptive=False, max_hops=256)
fts = FaultTolerantShardedSearch(sidx, mesh, n_replicas=2)
r0 = fts(jnp.asarray(Q), params)
assert r0.coverage == 1.0 and r0.failover == 0
fts.registry.mark_dead(1, replica=0)       # primary lost -> replica serves
r1 = fts(jnp.asarray(Q), params)
assert r1.coverage == 1.0 and r1.failover == 1 and r1.max_missed == 0
assert (np.asarray(r0.ids) == np.asarray(r1.ids)).all()
fts.registry.mark_dead(1, replica=1)       # replica lost too -> degrade
r2 = fts(jnp.asarray(Q), params)
assert abs(r2.coverage - 3/4) < 1e-9 and r2.max_missed == 5
fts.registry.mark_live(1, replica=0)       # recovery
r3 = fts(jnp.asarray(Q), params)
assert r3.coverage == 1.0 and r3.failover == 0
assert (np.asarray(r3.ids) == np.asarray(r0.ids)).all()
print("OK")
""")
    assert "OK" in out


@pytest.mark.faults
def test_sharded_resilient_server_degrades_explicitly():
    """The resilient server over a sharded index: shard death degrades
    coverage per-response (never silently), a merge-tier fault falls back
    to the other exact merge, and revival restores coverage=1.0."""
    out = _run("""
import os
from repro.core import BuildParams, SearchParams
from repro.serve import ResilienceConfig, ShardedResilientAnnServer
from repro.testing import FaultPlan, inject_search_faults
seed = int(os.environ.get("REPRO_FAULT_SEED", "0"))
rng = np.random.default_rng(seed)
X = rng.normal(size=(512, 16)).astype(np.float32)
Q = rng.normal(size=(12, 16)).astype(np.float32)
mesh = jax.make_mesh((4,), ("data",))
from repro.core.distributed import build_sharded
sidx = build_sharded(X, 4, BuildParams(max_degree=12, beam_width=24, t=8,
                                       iters=1, block=512))
params = SearchParams(k=5, l0=8, l_max=32, adaptive=False, max_hops=256,
                      beam_width=1)
srv = ShardedResilientAnnServer(sidx, params, mesh,
                                config=ResilienceConfig(backoff_s=0.0))
srv.submit_many(Q)
rs = srv.drain()
assert all(r.ok and r.coverage == 1.0 and r.max_missed == 0 for r in rs)
assert all(r.tier == "sharded/all_gather" for r in rs)

srv.kill_shard(2)                          # shard death: explicit degradation
srv.submit_many(Q)
rs = srv.drain()
assert all(r.ok and abs(r.coverage - 3/4) < 1e-9 and r.max_missed == 5
           for r in rs)

srv.revive_shard(2)                        # merge-time collective fault:
with inject_search_faults(                 # primary merge tier opens,
        srv, FaultPlan(fail_first=10**6,   # the other exact merge serves
                       match_backend="all_gather")) as inj:
    srv.submit_many(Q)
    rs = srv.drain()
assert inj.n_failed >= 1
assert all(r.ok and r.tier == "sharded/ring" and r.coverage == 1.0
           for r in rs)
print("OK")
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Deadline-based health checking (host-side: the registry/checker are pure
# numpy with injectable clocks, so no device subprocess is needed).
# ---------------------------------------------------------------------------


@pytest.mark.faults
def test_deadline_checker_kills_stale_replica_only():
    from repro.core.distributed import (DeadlineHealthChecker,
                                        ShardHealthRegistry)
    from repro.obs import MetricsRegistry, snapshot

    t = {"now": 0.0}
    reg = ShardHealthRegistry(4, n_replicas=2, clock=lambda: t["now"])
    m = MetricsRegistry()
    hc = DeadlineHealthChecker(reg, deadline_s=5.0, metrics=m)
    assert hc.check() == []                   # everything fresh at t=0

    t["now"] = 3.0                            # all beat except (1, 1) …
    for s in range(4):
        for r in range(2):
            if (s, r) != (1, 1):
                reg.heartbeat(s, r)
    t["now"] = 7.0                            # (1,1) age 7 > 5; rest age 4
    assert hc.check() == [(1, 1)]
    assert reg.coverage() == 1.0              # replica 0 still covers shard 1
    assert hc.n_killed == 1

    snap = snapshot(m)
    assert snap["counters"]["shard_marked_dead_total"] == 1
    assert snap["gauges"]['shard_live{shard="1"}'] == 1.0
    # per-shard rollup gauge tracks the freshest LIVE replica's age …
    assert abs(snap["gauges"]['shard_heartbeat_age_seconds{shard="1"}']
               - 4.0) < 1e-9
    # … while the per-replica family reports every slot's raw age (the
    # stale replica's 7.0 is visible even though the rollup hides it)
    assert abs(snap["gauges"][
        'shard_replica_heartbeat_age_seconds{replica="1",shard="1"}']
        - 7.0) < 1e-9
    assert abs(snap["gauges"][
        'shard_replica_heartbeat_age_seconds{replica="0",shard="1"}']
        - 4.0) < 1e-9
    evts = [e for e in snap["events"] if e["name"] == "shard_deadline_expired"]
    assert len(evts) == 1
    assert evts[0]["shard"] == 1 and evts[0]["replica"] == 1
    assert evts[0]["age_s"] > 5.0

    t["now"] = 10.0                           # now every survivor is stale
    killed = hc.check()
    assert (1, 1) not in killed               # dead slots are not re-killed
    assert len(killed) == 7
    assert reg.coverage() == 0.0
    assert snapshot(m)["gauges"]["shard_coverage"] == 0.0


@pytest.mark.faults
def test_zombie_heartbeat_does_not_revive_dead_slot():
    from repro.core.distributed import (DeadlineHealthChecker,
                                        ShardHealthRegistry)

    t = {"now": 0.0}
    reg = ShardHealthRegistry(2, clock=lambda: t["now"])
    hc = DeadlineHealthChecker(reg, deadline_s=1.0)
    t["now"] = 2.0
    assert len(hc.check()) == 2
    reg.heartbeat(0)                          # zombie's late beat: no revival
    assert reg.dead_shards() == [0, 1]
    assert hc.check() == []
    reg.mark_live(0)                          # explicit revival refreshes beat
    assert reg.live_shards() == [0]
    assert hc.check() == []                   # … so it is not instantly re-killed

    with pytest.raises(ValueError):
        DeadlineHealthChecker(reg, deadline_s=0.0)


@pytest.mark.faults
def test_sharded_server_health_deadline_auto_marks_dead():
    """Integration: a ShardedResilientAnnServer with ``health_deadline_s``
    auto-kills a shard whose heartbeats stop, degrading coverage explicitly
    on the next drain — no operator kill_shard needed."""
    out = _run("""
from repro.core import BuildParams, SearchParams
from repro.core.distributed import build_sharded
from repro.obs import MetricsRegistry, snapshot
from repro.serve import ResilienceConfig, ShardedResilientAnnServer
rng = np.random.default_rng(0)
X = rng.normal(size=(512, 16)).astype(np.float32)
Q = rng.normal(size=(12, 16)).astype(np.float32)
mesh = jax.make_mesh((4,), ("data",))
sidx = build_sharded(X, 4, BuildParams(max_degree=12, beam_width=24, t=8,
                                       iters=1, block=512))
params = SearchParams(k=5, l0=8, l_max=32, adaptive=False, max_hops=256,
                      beam_width=1)
t = {"now": 0.0}
m = MetricsRegistry()
srv = ShardedResilientAnnServer(sidx, params, mesh,
                                config=ResilienceConfig(backoff_s=0.0),
                                clock=lambda: t["now"],
                                health_deadline_s=5.0, metrics=m)
srv.submit_many(Q)
rs = srv.drain()
assert all(r.ok and r.coverage == 1.0 for r in rs)

t["now"] = 4.0
for s in (0, 1, 3):
    srv.heartbeat(s)                 # shard 2 goes silent
t["now"] = 7.0                       # age(2) = 7 > 5; others 3 < 5
srv.submit_many(Q)
rs = srv.drain()                     # checker sweeps before dispatch
assert srv.health_checker.n_killed == 1
assert all(r.ok and abs(r.coverage - 3/4) < 1e-9 for r in rs)
snap = snapshot(m)
assert snap["counters"]["shard_marked_dead_total"] == 1
assert snap["gauges"]['shard_live{shard="2"}'] == 0.0
assert abs(snap["gauges"]["shard_coverage"] - 3/4) < 1e-9

srv.revive_shard(2)                  # explicit revival refreshes the beat
srv.submit_many(Q)
rs = srv.drain()
assert all(r.ok and r.coverage == 1.0 for r in rs)
print("OK")
""", n_devices=4)
    assert "OK" in out
