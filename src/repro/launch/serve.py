"""ANN serving entry point — builds (or loads) a δ-EMQG index and serves a
query stream through the batched request loop.

    PYTHONPATH=src python -m repro.launch.serve --n 4000 --dim 48 \
        --queries 512 --alpha 1.2 --k 10

``--resilient`` runs the same stream through the resilience layer
(admission control, per-request deadlines, error-bounded degradation
ladder, circuit-breaker fallback — see ``repro.serve.resilience``) and
reports the resilience counters plus the worst δ error bound any response
was served under.

``--metrics`` attaches the unified observability layer (``repro.obs``):
the server emits the standard serve taxonomy (request-latency / queue-wait
histograms with p50/p95/p99, per-status response counters, degradation /
breaker transition counters, batch-aggregated ``n_dist_comps``/``n_hops``
Exp-5 counters, shard-liveness gauges, WAL timing families) plus
per-request spans, and the run ends with a Prometheus-text and a JSON
snapshot on stdout.  ``--metrics-every S`` additionally prints a one-line
stderr summary at most every S seconds while draining (implies
``--metrics``).

``--shards N`` serves a sharded index over N devices through
``ShardedResilientAnnServer``; ``--kill-shards 1,2`` stages a mid-stream
shard loss and ``--auto-repair`` (with ``--repair-budget`` /
``--store-dir``) lets the ``core.repair`` controller rebuild the lost
shards from a durable vector store, verify, and atomically re-install them
— the printed coverage trajectory returns to 1.0 without operator action.

Every result line names the device it ran on.  The persistent compilation
cache sits where ``repro.launch.cache.use_compile_cache`` puts it."""

from __future__ import annotations

import argparse
import math
import time

import jax
import numpy as np

from repro.core import BuildParams, SearchParams, build_emqg
from repro.core.distances import brute_force_knn
from repro.data import clustered_vectors
from repro.launch.cache import use_compile_cache
from repro.obs import (
    MetricsRegistry,
    PeriodicSummary,
    Tracer,
    declare_serve_metrics,
    to_json,
    to_prometheus,
)
from repro.serve import AnnServer, ResilienceConfig, ResilientAnnServer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=48)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--alpha", type=float, default=1.2)
    ap.add_argument("--max-degree", type=int, default=24)
    ap.add_argument("--beam", type=int, default=64)
    ap.add_argument("--delta", type=float, default=None,
                    help="fixed construction δ (default: adaptive δ_t rule; "
                         "a fixed δ makes the reported error bounds finite)")
    ap.add_argument("--resilient", action="store_true",
                    help="serve through the resilience layer")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (resilient mode)")
    ap.add_argument("--max-queue", type=int, default=4096,
                    help="admission-control queue cap (resilient mode)")
    ap.add_argument("--degrade-at", type=int, default=64,
                    help="queue depth that steps the ladder down one rung")
    ap.add_argument("--recover-at", type=int, default=8,
                    help="queue depth that steps the ladder back up")
    ap.add_argument("--rungs", type=int, default=4,
                    help="degradation-ladder depth (resilient mode)")
    ap.add_argument("--audit", action="store_true",
                    help="run the graph-invariant auditor (core.verify) on "
                         "the built index before serving; non-zero exit on "
                         "violations")
    ap.add_argument("--metrics", action="store_true",
                    help="attach the obs layer; print Prometheus-text and "
                         "JSON metric snapshots after serving")
    ap.add_argument("--metrics-every", type=float, default=0.0,
                    help="emit a one-line stderr metrics summary at most "
                         "every S seconds while serving (implies --metrics)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve a sharded δ-EMQG over N devices (0 = "
                         "single-node).  Needs N visible devices — on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N")
    ap.add_argument("--kill-shards", default="",
                    help="comma-separated shard ids killed after the first "
                         "third of the stream (sharded-mode chaos demo)")
    ap.add_argument("--auto-repair", action="store_true",
                    help="self-heal killed shards: rebuild from a durable "
                         "ShardVectorStore, verify, atomically install "
                         "(sharded mode)")
    ap.add_argument("--repair-budget", type=int, default=1,
                    help="max repair attempts per sweep (--auto-repair)")
    ap.add_argument("--store-dir", default=None,
                    help="ShardVectorStore directory (--auto-repair; "
                         "default: a temp dir created for the run)")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = jax.devices()[0]
    where = f"{dev.platform} ({dev.device_kind})"

    registry = tracer = summary = None
    if args.metrics or args.metrics_every > 0:
        registry = declare_serve_metrics(MetricsRegistry(),
                                         n_shards=max(args.shards, 1))
        tracer = Tracer()
        summary = PeriodicSummary(registry, args.metrics_every)

    if args.shards:
        return _serve_sharded(args, registry, tracer)

    print(f"[serve] building δ-EMQG over n={args.n} d={args.dim} …")
    base = clustered_vectors(args.n, args.dim, 48, seed=0)
    t0 = time.perf_counter()
    idx = build_emqg(base, BuildParams(
        max_degree=args.max_degree, beam_width=args.beam, delta=args.delta,
        t=args.beam // 2, iters=2, block=1024, align_degree=True),
        metrics=registry)
    print(f"[serve] built in {time.perf_counter() - t0:.1f}s "
          f"(mean degree {float(np.asarray(idx.graph.degrees()).mean()):.1f})")

    if args.audit:
        from repro.core.verify import audit
        rep = audit(idx.graph)
        print(rep.summary())
        if not rep.ok:
            return 1

    queries = clustered_vectors(args.queries, args.dim, 48, seed=1)
    gt_d, gt_i = brute_force_knn(queries, base, args.k)
    params = SearchParams(k=args.k, l0=args.k, l_max=256, alpha=args.alpha,
                          adaptive=True, max_hops=2048)

    def drive(srv, queries):
        """Submit + drain, chunked when a periodic summary is live so the
        heartbeat can fire between batches of a long replay."""
        if summary is None or summary.every_s <= 0:
            srv.submit_many(queries)
            return srv.drain()
        out = []
        chunk = max(srv.max_batch, 1)
        for s in range(0, len(queries), chunk):
            srv.submit_many(queries[s : s + chunk])
            out.extend(srv.drain())
            summary.tick()
        summary.tick(force=True)
        return out

    if args.resilient:
        cfg = ResilienceConfig(
            max_queue=args.max_queue,
            deadline_s=None if args.deadline_ms is None
            else args.deadline_ms / 1e3,
            degrade_depth=args.degrade_at, recover_depth=args.recover_at,
            n_rungs=args.rungs)
        srv = ResilientAnnServer(idx, params, config=cfg,
                                 max_batch=128, buckets=(32, 128),
                                 metrics=registry, tracer=tracer)
        _warm(srv)
        t0 = time.perf_counter()
        responses = drive(srv, queries)
        drive_s = time.perf_counter() - t0
        served = [(i, r) for i, r in enumerate(responses) if r.ok]
        ids = np.stack([r.ids for _, r in served]) if served else np.zeros((0, args.k))
        rec = np.mean([
            len(set(ids[j].tolist()) & set(gt_i[i].tolist())) / args.k
            for j, (i, _) in enumerate(served)]) if served else 0.0
        bounds = [r.delta_bound for _, r in served]
        worst = max(bounds) if bounds else math.inf
        s = srv.stats
        print(f"[serve] {s.n_requests} served / {len(responses)} submitted "
              f"in {s.n_batches} batches; recall@{args.k}={rec:.4f}; "
              f"{s.n_requests / drive_s:.1f} queries/s (host wall clock) "
              f"on {where}; "
              f"p_max_latency={s.max_latency_s * 1e3:.1f} ms")
        print(f"[serve] resilience: shed={s.n_shed} rejected={s.n_rejected} "
              f"degraded={s.n_degraded} retried={s.n_retried} "
              f"fallback={s.n_fallback} deadline_missed={s.n_deadline_missed} "
              f"failed={s.n_failed}; worst δ bound="
              f"{worst if math.isfinite(worst) else 'unbounded (δ unknown)'}")
        _dump_metrics(registry, tracer)
        return 0

    srv = AnnServer(idx, params, max_batch=128, buckets=(32, 128),
                    metrics=registry, tracer=tracer)
    t0 = time.perf_counter()
    results = drive(srv, queries)
    drive_s = time.perf_counter() - t0
    ids = np.stack([r[0] for r in results])
    rec = np.mean([len(set(ids[i].tolist()) & set(gt_i[i].tolist())) / args.k
                   for i in range(len(results))])
    print(f"[serve] {srv.stats.n_requests} requests in "
          f"{srv.stats.n_batches} batches; recall@{args.k}={rec:.4f}; "
          f"{srv.stats.n_requests / drive_s:.1f} queries/s (host wall clock) "
          f"on {where}; "
          f"p_max_latency={srv.stats.max_latency_s * 1e3:.1f} ms")
    _dump_metrics(registry, tracer)
    return 0


def _warm(srv) -> None:
    """Compile every ladder rung's program before traffic arrives, so an
    overload that steps the ladder down never waits on a compile."""
    t0 = time.perf_counter()
    n = srv.warm()
    print(f"[serve] warmed {n} programs (buckets x ladder rungs) in "
          f"{time.perf_counter() - t0:.1f}s")


def _serve_sharded(args, registry, tracer) -> int:
    """Sharded serving with optional mid-stream shard kills and self-healing
    repair — the CLI face of ``core.repair`` + ``ShardedResilientAnnServer``.

    The stream runs in three stages: healthy third, then ``--kill-shards``
    lands, then the tail — with ``--auto-repair`` the repair controller
    rebuilds the killed shards from the vector store before the next batch
    dispatches, so the printed coverage trajectory returns to 1.0 without
    an operator call."""
    import tempfile

    from jax.sharding import Mesh

    from repro.core.distributed import build_sharded
    from repro.serve import ShardedResilientAnnServer

    devs = jax.devices()
    if len(devs) < args.shards:
        print(f"[serve] need {args.shards} devices, have {len(devs)} — "
              "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{args.shards}")
        return 2
    mesh = Mesh(np.array(devs[: args.shards]), ("data",))
    bp = BuildParams(max_degree=args.max_degree, beam_width=args.beam,
                     delta=args.delta, t=args.beam // 2, iters=2, block=1024,
                     align_degree=True)
    print(f"[serve] building sharded δ-EMQG: n={args.n} d={args.dim} "
          f"S={args.shards} …")
    base = clustered_vectors(args.n, args.dim, 48, seed=0)
    t0 = time.perf_counter()
    sidx = build_sharded(base, args.shards, bp, quantized=True, seed=0)
    print(f"[serve] built in {time.perf_counter() - t0:.1f}s")

    store_dir = None
    if args.auto_repair:
        from repro.core.repair import ShardVectorStore
        store_dir = args.store_dir or tempfile.mkdtemp(prefix="shard_store_")
        ShardVectorStore.create(store_dir, base, args.shards, bp,
                                quantized=True, seed=0)
        print(f"[serve] vector store at {store_dir}")

    queries = clustered_vectors(args.queries, args.dim, 48, seed=1)
    gt_d, gt_i = brute_force_knn(queries, base, args.k)
    params = SearchParams(k=args.k, l0=args.k, l_max=256, alpha=args.alpha,
                          adaptive=True, max_hops=2048)
    repair_cfg = None
    if args.auto_repair:
        from repro.core.repair import RepairConfig
        repair_cfg = RepairConfig(budget_per_sweep=args.repair_budget)
    srv = ShardedResilientAnnServer(
        sidx, params, mesh, quantized=True, max_batch=128,
        buckets=(32, 128), metrics=registry, tracer=tracer,
        auto_repair=repair_cfg, vector_store=store_dir)
    _warm(srv)

    kill = [int(x) for x in args.kill_shards.split(",") if x.strip()]
    stages = np.array_split(np.arange(len(queries)), 3)
    responses, coverage_traj = [], []
    for stage, idxs in enumerate(stages):
        if stage == 1 and kill:
            for s in kill:
                srv.kill_shard(s)
            print(f"[serve] killed shards {kill} "
                  f"(coverage now {srv.coverage:.2f})")
        if idxs.size:
            srv.submit_many(queries[idxs])
            responses.extend(srv.drain())
        coverage_traj.append(srv.coverage)
    served = [(i, r) for i, r in enumerate(responses) if r.ok]
    rec = np.mean([
        len(set(r.ids.tolist()) & set(gt_i[i].tolist())) / args.k
        for i, r in served]) if served else 0.0
    worst_cov = min((r.coverage for _, r in served), default=1.0)
    print(f"[serve] {len(served)} served / {len(responses)} submitted; "
          f"recall@{args.k}={rec:.4f}; coverage trajectory "
          f"{[round(c, 2) for c in coverage_traj]} (worst response "
          f"{worst_cov:.2f})")
    if srv.repair is not None:
        print(f"[serve] repair: {srv.repair.n_repaired} repaired, "
              f"{srv.repair.n_failed} failed attempts, "
              f"{srv.repair.n_sweeps} sweeps; final coverage "
              f"{srv.coverage:.2f}")
    elif kill:
        print(f"[serve] no auto-repair: coverage stays {srv.coverage:.2f} "
              "until an operator rebuilds")
    _dump_metrics(registry, tracer)
    return 0


def _dump_metrics(registry, tracer) -> None:
    if registry is None:
        return
    print("=== metrics (prometheus text) ===")
    print(to_prometheus(registry), end="")
    print("=== metrics (json) ===")
    print(to_json(registry, tracer))


if __name__ == "__main__":
    raise SystemExit(main())
