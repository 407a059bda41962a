"""Unified observability layer: metrics registry, span tracing, exporters.

One substrate for every layer of the system — the servers
(``repro.serve``), the builder (``core.build_approx``), the WAL
(``core.updates``), shard health (``core.distributed``) and the benchmark
harness all observe into the same registry types, so "what does a request
cost" has a single answer with a single bucket math.

Metric taxonomy (names are stable API — the README documents them):

======================================  =========  ==============================
name                                    kind       meaning
======================================  =========  ==============================
serve_request_latency_seconds           histogram  submit → response, monotonic
serve_queue_wait_seconds                histogram  submit → batch dispatch
serve_batch_execute_seconds             histogram  device search per batch
serve_batch_size                        histogram  requests per dispatched batch
serve_responses_total{status}           counter    ok/rejected/shed/deadline/failed
serve_degradation_transitions_total
  {direction,rung}                      counter    ladder steps (event: bound)
serve_breaker_transitions_total
  {from,to}                             counter    circuit-breaker tier moves
serve_rung                              gauge      current ladder rung
search_dist_comps_total                 counter    exact distance evals (Exp-5)
search_approx_comps_total               counter    quantized evals (δ-EMQG)
search_probes_total                     counter    ids promoted to the exact
                                                   tier (probing engine only)
search_hops_total                       counter    expansions
search_encounters_total                 counter    pre-dedup candidate encounters
search_saturated_total                  counter    queries whose adaptive l capped
search_row_iters_total                  counter    lock-step iterations live rows
                                                   were active in (Σ n_iters)
search_slot_iters_total                 counter    lock-step iterations × bucket
                                                   rows (the loop's slots)
search_resident_iters_total             counter    slots the compacting loop ran:
                                                   Σ n_resident, pads included
search_final_l                          histogram  per-query final beam length
shard_live{shard}                       gauge      1 = some replica live
shard_coverage                          gauge      live logical shards / S
shard_failover                          gauge      shards served by non-primary
shard_heartbeat_age_seconds{shard}      gauge      min age over live replicas
shard_replica_heartbeat_age_seconds
  {shard,replica}                       gauge      raw per-slot heartbeat age
shard_marked_dead_total                 counter    health-checker kills
repair_started_total                    counter    repair attempts begun
repair_succeeded_total                  counter    verified installs completed
repair_failed_total                     counter    contained repair failures
shard_under_repair{shard}               gauge      1 from first attempt→success
repair_duration_seconds                 histogram  successful repair wall time
wal_append_seconds                      histogram  journal record commit
wal_fsync_seconds                       histogram  fsync inside atomic writes
wal_records_total{op}                   counter    committed journal records
checkpoint_save_seconds                 histogram  full snapshot commit
checkpoint_restore_seconds              histogram  recover() restore+replay
build_phase_seconds{phase}              histogram  builder phase wall time
build_nodes_total                       counter    nodes processed by the builder
======================================  =========  ==============================

Span taxonomy: ``serve.request`` (child ``serve.queue_wait``) per request;
``serve.batch`` per dispatched batch (attribute ``iters``: the batch's
lock-step iteration count) with children ``serve.batch_form``,
``serve.device_execute`` and ``serve.merge``.  ``serve.device_execute``
holds ``serve.put`` (queries to the device), ``serve.launch`` (the search
call, up to its return) and ``serve.fetch`` (the one blocking read of the
result), and ``shard{shard,live}`` under sharded fan-out.  The tracer
writes each of these spans, except the retroactive request spans, into a
running ``jax.profiler`` trace too (the mirror).

Device phases: the beam engines' loop bodies run each phase under a
``jax.named_scope`` — ``hop.select``, ``hop.expand``, ``hop.visited``,
``hop.distance``, ``hop.merge``, ``hop.transition``, and in the probing
engine ``hop.estimate`` (the RaBitQ estimates) — so the compiled ops'
metadata names the phase each one belongs to.  Between the stages of the
compacting loop (``core/lockstep.py``), the gather of the active rows and
the write-back of a stage's rows run under ``hop.compact``.

Everything here is stdlib-only (the mirror imports ``jax`` at the first
span, and is off where it cannot) and observation-only: enabling metrics
can not change search results (pinned bit-identical by
``tests/test_obs.py``).
"""

from .exporters import (  # noqa: F401
    PeriodicSummary,
    snapshot,
    summary_line,
    to_json,
    to_prometheus,
)
from .metrics import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS_S,
    DEFAULT_WORK_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Timer,
)
from .tracing import Span, Tracer  # noqa: F401


def declare_serve_metrics(registry: MetricsRegistry,
                          n_shards: int = 1) -> MetricsRegistry:
    """Pre-register the full serve taxonomy so exports have a stable schema
    from the first scrape (families exist with zero samples before the
    first request arrives — standard exporter practice)."""
    registry.histogram("serve_request_latency_seconds",
                       help="submit-to-response latency (monotonic clock)")
    registry.histogram("serve_queue_wait_seconds",
                       help="submit-to-dispatch queue wait")
    registry.histogram("serve_batch_execute_seconds",
                       help="device search time per batch")
    registry.histogram("serve_batch_size", buckets=DEFAULT_WORK_BUCKETS,
                       help="requests per dispatched batch")
    for status in ("ok", "rejected", "shed", "deadline", "failed"):
        registry.counter("serve_responses_total", {"status": status},
                         help="responses by terminal status")
    registry.counter("serve_degradation_transitions_total",
                     {"direction": "down", "rung": "1"},
                     help="degradation-ladder transitions")
    registry.gauge("serve_rung", help="current degradation-ladder rung")
    registry.counter("search_dist_comps_total",
                     help="exact distance evaluations (Exp-5 metric)")
    registry.counter("search_approx_comps_total",
                     help="quantized distance evaluations")
    registry.counter("search_hops_total", help="search expansions")
    registry.counter("search_encounters_total",
                     help="pre-dedup candidate encounters")
    registry.counter("search_saturated_total",
                     help="queries whose adaptive l hit the cap")
    registry.counter("search_row_iters_total",
                     help="lock-step iterations live rows were active in")
    registry.counter("search_slot_iters_total",
                     help="lock-step iterations times bucket rows")
    registry.counter("search_resident_iters_total",
                     help="row slots the compacting lock-step loop ran")
    registry.histogram("search_final_l", buckets=DEFAULT_WORK_BUCKETS,
                       help="per-query final beam length")
    registry.gauge("shard_coverage",
                   help="live logical shards / total").set(1.0)
    registry.gauge("shard_failover",
                   help="shards served by a non-primary replica")
    for s in range(n_shards):
        registry.gauge("shard_live", {"shard": s},
                       help="1 if some replica of the shard is live").set(1.0)
    registry.counter("shard_marked_dead_total",
                     help="shards auto-killed by the health checker")
    registry.counter("repair_started_total",
                     help="shard repair attempts begun")
    registry.counter("repair_succeeded_total",
                     help="shard repairs verified and installed")
    registry.counter("repair_failed_total",
                     help="shard repair attempts that failed (will retry)")
    registry.histogram("repair_duration_seconds",
                       help="wall time of successful shard repairs")
    registry.histogram("wal_append_seconds",
                       help="WAL record commit (payload+manifest)")
    registry.histogram("wal_fsync_seconds",
                       help="fsync inside atomic WAL/meta writes")
    registry.histogram("checkpoint_save_seconds",
                       help="full snapshot commit")
    registry.histogram("checkpoint_restore_seconds",
                       help="recover(): restore + WAL replay")
    return registry


def record_search_result(registry: MetricsRegistry, res,
                         n_live: int = None) -> None:
    """Aggregate one batch's ``SearchResult`` counters into host-side
    metrics.  ``n_live`` restricts the aggregation to the first ``n_live``
    rows (padded rows repeat the last real query — counting them would
    double-bill the pad).  Read-only on ``res``; pass it fetched to the host
    (``jax.device_get``), or each field is read from the device in turn.

    ``n_iters``, where the result has it, gives the lock-step counters: the
    live rows' iterations, and the loop's trip count (the largest over all
    rows, pads included) times the rows the loop ran on.  ``n_resident``
    gives the slots the loop, compacted as rows finish, actually ran: its
    sum over all rows, pads included.
    """
    import numpy as np  # deferred: keep `repro.obs` importable stdlib-only

    def rows(x):
        a = np.asarray(x)
        return a[:n_live] if n_live is not None else a

    registry.counter("search_dist_comps_total").inc(
        float(rows(res.n_dist_comps).sum()))
    registry.counter("search_hops_total").inc(float(rows(res.n_hops).sum()))
    if getattr(res, "n_approx_comps", None) is not None:
        registry.counter("search_approx_comps_total").inc(
            float(rows(res.n_approx_comps).sum()))
    if getattr(res, "n_probes", None) is not None:
        registry.counter("search_probes_total").inc(
            float(rows(res.n_probes).sum()))
    if getattr(res, "n_encounters", None) is not None:
        registry.counter("search_encounters_total").inc(
            float(rows(res.n_encounters).sum()))
    if getattr(res, "n_iters", None) is not None:
        it = np.asarray(res.n_iters)
        registry.counter("search_row_iters_total").inc(
            float(rows(it).sum()))
        registry.counter("search_slot_iters_total").inc(
            float(it.max(initial=0)) * it.size)
    if getattr(res, "n_resident", None) is not None:
        registry.counter("search_resident_iters_total").inc(
            float(np.asarray(res.n_resident).sum()))
    registry.counter("search_saturated_total").inc(
        float(rows(res.saturated).sum()))
    registry.histogram("search_final_l", buckets=DEFAULT_WORK_BUCKETS) \
        .observe_many(rows(res.final_l))
