"""Observability layer: histogram math, span nesting, exporters, and the
observation-only invariant (metrics on vs off must be bit-identical).

The acceptance surface here is deliberately wide: the metric names are
stable API (README §Observability), so the exporter tests grep for the
exact families an operator's dashboards would scrape."""

import dataclasses
import glob
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SearchParams, build_exact
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS_S,
    DEFAULT_WORK_BUCKETS,
    Histogram,
    MetricsRegistry,
    PeriodicSummary,
    Timer,
    Tracer,
    declare_serve_metrics,
    snapshot,
    summary_line,
    to_json,
    to_prometheus,
)
from repro.serve import AnnServer, ResilienceConfig, ResilientAnnServer

PARAMS = SearchParams(k=5, l0=8, l_max=64, alpha=1.4, adaptive=True,
                      max_hops=512, beam_width=4)


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(11)
    base = rng.normal(size=(300, 16)).astype(np.float32)
    with pytest.warns(UserWarning):          # degree cap on a dense corpus
        graph = build_exact(base, delta=0.15, max_degree=12)
    queries = rng.normal(size=(48, 16)).astype(np.float32)
    return {"graph": graph, "queries": queries}


# ---------------------------------------------------------------------------
# Histogram math.
# ---------------------------------------------------------------------------


def test_histogram_bucket_placement():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [2, 1, 1]             # upper edges are inclusive
    assert h.overflow == 1
    assert h.count == 5
    assert h.min == 0.5 and h.max == 100.0
    # cumulative export ends with the +Inf bucket covering everything
    cum = h.cumulative()
    assert cum[-1] == (math.inf, 5)
    assert [c for _, c in cum] == sorted(c for _, c in cum)


def test_histogram_quantiles_track_numpy_within_bucket_resolution():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6.0, sigma=1.0, size=5000)  # ms-scale latencies
    h = Histogram()                           # default latency ladder
    for v in vals:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        exact = float(np.percentile(vals, 100 * q))
        # doubling buckets ⇒ interpolated estimate within one bucket (2×)
        assert exact / 2 <= est <= exact * 2, (q, est, exact)


def test_histogram_overflow_quantile_reports_observed_max():
    h = Histogram(bounds=(1.0,))
    h.observe(5.0)
    h.observe(7.5)
    assert h.quantile(0.99) == 7.5            # not +Inf, not the edge


def test_observe_many_matches_observe():
    """The vectorised bucket count gives the histogram a loop of
    ``observe`` gives: buckets, overflow, count, drops, min, max, sum."""
    rng = np.random.default_rng(4)
    vals = np.concatenate([rng.integers(1, 40000, 500).astype(float),
                           [2.0, 4.0, 16384.0, 1e9, float("nan")]])
    a, b = Histogram(DEFAULT_WORK_BUCKETS), Histogram(DEFAULT_WORK_BUCKETS)
    for v in vals:
        a.observe(v)
    b.observe_many(vals)
    assert (a.counts, a.overflow, a.count, a.n_dropped, a.min, a.max) == \
        (b.counts, b.overflow, b.count, b.n_dropped, b.min, b.max)
    assert a.sum == b.sum                     # integers: exact either way
    b.observe_many(np.array([]))
    assert b.count == a.count


def test_histogram_nan_dropped_not_raised():
    h = Histogram(bounds=(1.0,))
    h.observe(float("nan"))
    h.observe(0.5)
    assert h.count == 1 and h.n_dropped == 1


def test_histogram_empty_and_validation():
    assert Histogram().quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        Histogram(bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram().quantile(1.5)


# ---------------------------------------------------------------------------
# Registry: counters, gauges, labels, events, timer.
# ---------------------------------------------------------------------------


def test_counter_monotone_gauge_not():
    r = MetricsRegistry()
    c = r.counter("reqs_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3.0


def test_labels_create_distinct_children_and_get_or_create():
    r = MetricsRegistry()
    a = r.counter("resp_total", {"status": "ok"})
    b = r.counter("resp_total", {"status": "failed"})
    a.inc(3)
    assert b.value == 0
    # same labels in any order → the same child object
    r2 = r.counter("resp_total", {"status": "ok"})
    assert r2 is a


def test_kind_conflict_raises():
    r = MetricsRegistry()
    r.counter("x_total")
    with pytest.raises(TypeError):
        r.gauge("x_total")


def test_event_ring_and_auto_counter():
    r = MetricsRegistry(max_events=2)
    r.event("ladder_step", rung=1, reason="queue_depth=70")
    r.event("ladder_step", rung=2, reason="queue_depth=90")
    r.event("ladder_step", rung=1, reason="drained")
    assert len(r.events) == 2                 # bounded ring
    assert r.events[-1]["reason"] == "drained"
    assert r.counter("ladder_step_total").value == 3


def test_timer_observes_elapsed():
    r = MetricsRegistry()
    with r.timer("op_seconds") as t:
        pass
    assert t.elapsed >= 0
    assert r.histogram("op_seconds").count == 1
    assert Timer.now() > 0


# ---------------------------------------------------------------------------
# Tracing: nesting, explicit parents, retroactive spans.
# ---------------------------------------------------------------------------


def test_lexical_spans_nest():
    tr = Tracer()
    with tr.span("batch") as b:
        with tr.span("execute") as e:
            pass
    assert e.parent_id == b.span_id
    assert b.parent_id is None
    assert [s.name for s in tr.children_of(b)] == ["execute"]
    assert all(s.finished for s in tr.finished)


def test_explicit_parent_beats_stack_and_activate_bridges():
    tr = Tracer()
    root = tr.start_span("root")
    with tr.span("other"):
        child = tr.start_span("child", parent=root)   # explicit wins
    assert child.parent_id == root.span_id
    # activate/deactivate: non-lexical parenting across a call boundary
    tr.activate(root)
    inner = tr.start_span("fanout")
    tr.deactivate(root)
    assert inner.parent_id == root.span_id


def test_mirrored_spans_on_profiler_host_plane(tmp_path):
    """A mirrored span is an annotation on the host plane of a profiler
    trace; a retroactive one (``start=`` given) is not."""
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("serve.batch"):
            tr.end_span(tr.start_span("serve.put"))
        tr.end_span(tr.start_span("serve.request", start=Timer.now()))
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0])
    cpu = pd.find_plane_with_name("/host:CPU")
    names = {e.name for line in cpu.lines for e in line.events}
    assert {"serve.batch", "serve.put"} <= names
    assert "serve.request" not in names
    assert all(s.annotation is None for s in tr.finished)


def test_spans_without_jax_are_not_mirrored(monkeypatch):
    """Where ``jax.profiler`` cannot be imported, spans time as before and
    carry no annotation."""
    from repro.obs import tracing

    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    tracing._trace_annotation.cache_clear()
    try:
        tr = Tracer()
        with tr.span("serve.batch") as s:
            assert s.annotation is None
        assert tr.by_name("serve.batch")[0].finished
    finally:
        tracing._trace_annotation.cache_clear()


def test_retroactive_end_and_ring_bound():
    tr = Tracer(max_spans=2)
    s = tr.start_span("request")
    s.start = 10.0
    tr.end_span(s, end=12.5)
    assert s.duration_s == 2.5
    tr.end_span(s, end=99.0)                  # double-end is a no-op
    assert s.end == 12.5
    for i in range(3):
        tr.end_span(tr.start_span(f"s{i}"))
    assert len(tr.finished) == 2              # bounded


# ---------------------------------------------------------------------------
# Exporters.
# ---------------------------------------------------------------------------


def test_prometheus_text_format():
    r = MetricsRegistry()
    r.counter("resp_total", {"status": "ok"}, help="responses").inc(4)
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    txt = to_prometheus(r)
    assert "# TYPE resp_total counter" in txt
    assert 'resp_total{status="ok"} 4.0' in txt
    assert 'lat_seconds_bucket{le="0.1"} 1' in txt
    assert 'lat_seconds_bucket{le="+Inf"} 2' in txt
    assert "lat_seconds_count 2" in txt
    assert 'lat_seconds{quantile="0.95"}' in txt


def test_json_snapshot_round_trip():
    r = MetricsRegistry()
    r.counter("c_total").inc(2)
    r.gauge("g").set(0.5)
    r.histogram("h_seconds", buckets=(1.0,)).observe(0.3)
    r.event("evt", detail="x")
    tr = Tracer()
    tr.end_span(tr.start_span("request", seq=0))
    snap = json.loads(to_json(r, tr))
    assert snap["counters"]["c_total"] == 2.0
    assert snap["counters"]["evt_total"] == 1.0
    assert snap["gauges"]["g"] == 0.5
    assert snap["histograms"]["h_seconds"]["count"] == 1
    assert snap["histograms"]["h_seconds"]["p50"] >= 0
    assert snap["events"][0]["detail"] == "x"
    assert snap["spans"][0]["name"] == "request"
    # exporting is read-only: a second export is identical
    assert to_json(r, tr) == to_json(r, tr)


def test_summary_line_and_periodic_gate():
    r = declare_serve_metrics(MetricsRegistry())
    r.histogram("serve_request_latency_seconds").observe(0.004)
    line = summary_line(r)
    assert line.startswith("[obs] req=1")
    # injectable clock: emits once per interval, force overrides
    t = {"now": 0.0}
    out = []

    class _S:
        def write(self, s):
            out.append(s)

        def flush(self):
            pass

    ps = PeriodicSummary(r, 10.0, stream=_S(), clock=lambda: t["now"])
    assert ps.tick() is None                  # interval not elapsed
    t["now"] = 11.0
    assert ps.tick() is not None
    assert ps.tick() is None                  # gated again
    assert ps.tick(force=True) is not None


def test_declared_schema_covers_acceptance_families():
    snap = snapshot(declare_serve_metrics(MetricsRegistry(), n_shards=2))
    hists, gauges, counters = (snap["histograms"], snap["gauges"],
                               snap["counters"])
    for h in ("serve_request_latency_seconds", "serve_queue_wait_seconds",
              "wal_append_seconds", "wal_fsync_seconds"):
        assert h in hists, h
    assert 'shard_live{shard="0"}' in gauges
    assert 'shard_live{shard="1"}' in gauges
    assert "shard_coverage" in gauges
    for c in ("search_dist_comps_total", "search_hops_total",
              'serve_responses_total{status="ok"}'):
        assert c in counters, c
    assert any(k.startswith("serve_degradation_transitions_total")
               for k in counters)


# ---------------------------------------------------------------------------
# Instrumented serving: taxonomy populated, spans linked, results unchanged.
# ---------------------------------------------------------------------------


def test_ann_server_populates_taxonomy_and_spans(tiny):
    m, tr = MetricsRegistry(), Tracer()
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,),
                    metrics=m, tracer=tr)
    srv.submit_many(tiny["queries"])
    out = srv.drain()
    n = len(tiny["queries"])
    assert len(out) == n
    snap = snapshot(m)
    assert snap["histograms"]["serve_request_latency_seconds"]["count"] == n
    assert snap["histograms"]["serve_queue_wait_seconds"]["count"] == n
    assert snap["counters"]['serve_responses_total{status="ok"}'] == n
    assert snap["counters"]["search_dist_comps_total"] > 0
    assert snap["counters"]["search_hops_total"] > 0
    assert snap["histograms"]["search_final_l"]["count"] == n
    # spans: every request span has a queue-wait child; batches decompose
    reqs = tr.by_name("serve.request")
    assert len(reqs) == n
    for rs in reqs[:4]:
        kids = tr.children_of(rs)
        assert [k.name for k in kids] == ["serve.queue_wait"]
        assert kids[0].end <= rs.end
    batches = tr.by_name("serve.batch")
    assert len(batches) == srv.stats.n_batches
    names = {s.name for b in batches for s in tr.children_of(b)}
    assert {"serve.batch_form", "serve.device_execute",
            "serve.merge"} <= names


def test_device_execute_split_and_batch_iters(tiny):
    """``serve.device_execute`` holds the put, the launch and the fetch;
    ``serve.batch`` carries the batch's lock-step iterations, and the
    lock-step counters add up from them."""
    m, tr = MetricsRegistry(), Tracer()
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,),
                    metrics=m, tracer=tr)
    srv.submit_many(tiny["queries"])          # 48 → batches of 32 and 16
    srv.drain()
    for e in tr.by_name("serve.device_execute"):
        kids = tr.children_of(e)
        assert [k.name for k in kids] == ["serve.put", "serve.launch",
                                          "serve.fetch"]
        assert e.start <= kids[0].start and kids[-1].end <= e.end
    iters = [b.attrs["iters"] for b in tr.by_name("serve.batch")]
    assert len(iters) == 2 and min(iters) > 0
    slots = m.counter("search_slot_iters_total").value
    assert slots == 32 * iters[0] + 32 * iters[1]
    rows = m.counter("search_row_iters_total").value
    assert 0 < rows <= 32 * iters[0] + 16 * iters[1]


def test_record_search_result_lockstep_counters():
    """Live rows' iterations, and the trip count (pads included) times the
    bucket's rows."""
    from types import SimpleNamespace

    from repro.obs import record_search_result

    z = np.zeros(4, np.int32)
    res = SimpleNamespace(n_dist_comps=z, n_hops=z, n_approx_comps=None,
                          n_encounters=None, saturated=z,
                          final_l=np.array([10, 12, 40, 40]),
                          n_iters=np.array([3, 5, 2, 7], np.int32))
    m = MetricsRegistry()
    record_search_result(m, res, n_live=3)
    assert m.counter("search_row_iters_total").value == 10
    assert m.counter("search_slot_iters_total").value == 7 * 4
    assert m.histogram("search_final_l", buckets=DEFAULT_WORK_BUCKETS) \
        .count == 3
    res.n_iters = None                        # a result made without the loop
    record_search_result(m, res, n_live=3)
    assert m.counter("search_slot_iters_total").value == 7 * 4


def test_resident_iters_counter_sums_n_resident(tiny):
    """``search_resident_iters_total`` is declared with the serve schema and
    adds a batch's ``n_resident`` over every row, pads included: the slots
    the compacting loop ran, no more than the lock-step slots."""
    m = declare_serve_metrics(MetricsRegistry())
    assert _has_family(m, "search_resident_iters_total")
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=48, buckets=(48,),
                    metrics=m)
    srv.submit_many(tiny["queries"][:40])     # 40 live rows, 8 pad
    srv.drain()
    qs = np.concatenate([tiny["queries"][:40],
                         np.repeat(tiny["queries"][39:40], 8, axis=0)])
    r = jax.device_get(srv._search(jnp.asarray(qs)))
    resident = m.counter("search_resident_iters_total").value
    assert resident == int(r.n_resident.sum()) > 0
    assert resident <= m.counter("search_slot_iters_total").value


def _has_family(registry, name):
    return any(fam == name for fam, *_ in registry.families())


def test_probes_counted_for_probing_engine_only(tiny):
    """``search_probes_total`` is the probing engine's promotions to its
    exact tier over the live rows (every exact distance but the start's);
    the exact engine records none, so the family is absent."""
    from repro.core import probing_search
    from repro.core.emqg import from_graph

    index = from_graph(tiny["graph"])
    m = MetricsRegistry()
    srv = AnnServer(index, PARAMS, max_batch=32, buckets=(32,), metrics=m)
    srv.submit_many(tiny["queries"][:20])
    srv.drain()
    res = probing_search(index, jnp.asarray(tiny["queries"][:20]), PARAMS)
    n_probes = np.asarray(res.n_probes)
    np.testing.assert_array_equal(n_probes,
                                  np.asarray(res.n_dist_comps) - 1)
    assert (n_probes > 0).all()
    assert m.counter("search_probes_total").value == n_probes.sum()
    assert 0 < n_probes.sum() < m.counter("search_hops_total").value

    m2 = MetricsRegistry()
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,),
                    metrics=m2)
    srv.submit_many(tiny["queries"][:20])
    srv.drain()
    assert _has_family(m2, "search_hops_total")
    assert not _has_family(m2, "search_probes_total")


def test_emqg_build_events_align_and_quantize():
    """The ``align_degree`` event counts the short rows and the edges it
    added; ``build_emqg`` times the RaBitQ fit as a ``quantize`` event."""
    from repro.core import BuildParams, build_emqg

    rng = np.random.default_rng(5)
    base = rng.normal(size=(256, 8)).astype(np.float32)
    m = MetricsRegistry()
    idx = build_emqg(base, BuildParams(max_degree=12, beam_width=16, t=12,
                                       iters=1, delta=0.2, block=128),
                     metrics=m)
    ev = {e["phase"]: e for e in m.events if e["name"] == "build_progress"}
    align = ev["align_degree"]
    deg = np.asarray(idx.graph.degrees())
    assert 0 < align["deficient"] <= 256
    assert align["padded"] > 0
    assert (deg == 12).all()
    q = ev["quantize"]
    assert q["nodes"] == 256 and q["elapsed_s"] > 0
    assert list(ev)[-1] == "quantize"


def test_pad_rows_not_double_billed(tiny):
    """A 5-request batch padded to bucket 32 must aggregate device counters
    over 5 rows, not 32."""
    m = MetricsRegistry()
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,),
                    metrics=m)
    srv.submit_many(tiny["queries"][:5])
    srv.drain()
    assert m.histogram("search_final_l",
                       buckets=DEFAULT_WORK_BUCKETS).count == 5


def _ids_dists(out):
    return (np.stack([np.asarray(i) for i, _ in out]),
            np.stack([np.asarray(d) for _, d in out]))


def test_metrics_on_vs_off_bit_identical_plain(tiny, tmp_path):
    """Metrics, spans and their mirror into a running profiler trace leave
    every field of the result as it was, the lock-step counter included.
    Unobserved, a batch fetches only the fields its answers read."""
    off = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,))
    on = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,),
                   metrics=declare_serve_metrics(MetricsRegistry()),
                   tracer=Tracer())
    off.submit_many(tiny["queries"])
    on.submit_many(tiny["queries"])
    ids0, d0 = _ids_dists(off.drain())
    qs = tiny["queries"][:32]
    r0 = jax.device_get(off._search(jnp.asarray(qs)))
    fetched = off._execute(qs)
    for f in dataclasses.fields(fetched):
        if f.name in AnnServer.ANSWER_FIELDS:
            np.testing.assert_array_equal(getattr(fetched, f.name),
                                          getattr(r0, f.name))
        else:
            assert getattr(fetched, f.name) is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        ids1, d1 = _ids_dists(on.drain())
        r1 = on._execute(qs)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(d0, d1)     # bit-identical, not allclose
    for f in dataclasses.fields(r0):
        np.testing.assert_array_equal(getattr(r0, f.name),
                                      getattr(r1, f.name))
    assert on.tracer.by_name("serve.batch")[0].attrs["iters"] \
        == int(r0.n_iters.max())


def test_metrics_on_vs_off_bit_identical_resilient(tiny):
    cfg = ResilienceConfig(backoff_s=0.0)
    off = ResilientAnnServer(tiny["graph"], PARAMS, config=cfg,
                             max_batch=32, buckets=(32,))
    on = ResilientAnnServer(tiny["graph"], PARAMS, config=cfg,
                            max_batch=32, buckets=(32,),
                            metrics=declare_serve_metrics(MetricsRegistry()),
                            tracer=Tracer())
    off.submit_many(tiny["queries"])
    on.submit_many(tiny["queries"])
    r0, r1 = off.drain(), on.drain()
    assert all(r.ok for r in r0) and all(r.ok for r in r1)
    np.testing.assert_array_equal(np.stack([r.ids for r in r0]),
                                  np.stack([r.ids for r in r1]))
    np.testing.assert_array_equal(np.stack([r.dists for r in r0]),
                                  np.stack([r.dists for r in r1]))


def test_resilient_ladder_transitions_recorded(tiny):
    """Overload → the ladder steps down; the transition must land as a
    labeled counter + a structured event carrying the δ bound."""
    m = MetricsRegistry()
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=ResilienceConfig(degrade_depth=8, recover_depth=2, n_rungs=3,
                                backoff_s=0.0),
        max_batch=8, buckets=(8,), metrics=m, tracer=Tracer())
    srv.submit_many(tiny["queries"])          # 48 deep ≫ degrade_depth
    srv.drain()
    snap = snapshot(m)
    downs = [k for k in snap["counters"]
             if k.startswith("serve_degradation_transitions_total")
             and 'direction="down"' in k]
    assert downs and sum(snap["counters"][k] for k in downs) > 0
    evts = [e for e in snap["events"]
            if e["name"] == "serve_degradation_transition"]
    assert evts
    assert {"from_rung", "rung", "direction", "reason",
            "delta_bound"} <= set(evts[0])
    assert "serve_rung" in snap["gauges"]


# ---------------------------------------------------------------------------
# WAL / checkpoint timings.
# ---------------------------------------------------------------------------


def test_journal_wal_and_checkpoint_timed(tmp_path, tiny):
    from repro.core import BuildParams
    from repro.core.updates import JournaledLiveIndex, as_live, recover

    m = MetricsRegistry()
    live = as_live(tiny["graph"],
                   BuildParams(max_degree=12, beam_width=20, t=10, iters=1,
                               block=128))
    j = JournaledLiveIndex.create(live, str(tmp_path), metrics=m)
    rng = np.random.default_rng(3)
    j.insert(rng.normal(size=(2, 16)).astype(np.float32))
    j.insert(rng.normal(size=(2, 16)).astype(np.float32))
    j.checkpoint()
    snap = snapshot(m)
    assert snap["histograms"]["wal_append_seconds"]["count"] == 2
    assert snap["histograms"]["wal_fsync_seconds"]["count"] > 0
    assert snap["counters"]['wal_records_total{op="insert"}'] == 2
    assert snap["histograms"]["checkpoint_save_seconds"]["count"] == 2

    m2 = MetricsRegistry()
    j2, info = recover(str(tmp_path), metrics=m2)
    assert j2.n_live == j.n_live
    assert info["elapsed_s"] >= 0
    assert snapshot(m2)["histograms"]["checkpoint_restore_seconds"]["count"] == 1


# ---------------------------------------------------------------------------
# Build events.
# ---------------------------------------------------------------------------


def test_build_emits_structured_phases(tiny):
    from repro.core import BuildParams, build_approx

    rng = np.random.default_rng(5)
    base = rng.normal(size=(200, 8)).astype(np.float32)
    m = MetricsRegistry()
    build_approx(base, BuildParams(max_degree=8, beam_width=16, t=8, iters=1,
                                   block=128), metrics=m)
    phases = [e["phase"] for e in m.events if e["name"] == "build_progress"]
    assert "bootstrap" in phases
    assert any(p.startswith("refine_iter") for p in phases)
    snap = snapshot(m)
    assert any(k.startswith("build_phase_seconds") for k in snap["histograms"])
    assert snap["counters"]["build_nodes_total"] > 0


# ---------------------------------------------------------------------------
# CLI: the acceptance snapshot.
# ---------------------------------------------------------------------------


def test_serve_cli_metrics_snapshot(capsys):
    from repro.launch.serve import main

    rc = main(["--n", "400", "--dim", "8", "--queries", "24", "--k", "5",
               "--beam", "16", "--max-degree", "8", "--metrics"])
    assert rc == 0
    outp = capsys.readouterr().out
    prom = outp.split("=== metrics (prometheus text) ===")[1] \
               .split("=== metrics (json) ===")[0]
    for family in ("serve_request_latency_seconds_bucket",
                   'serve_request_latency_seconds{quantile="0.5"}',
                   'serve_request_latency_seconds{quantile="0.99"}',
                   "serve_queue_wait_seconds_bucket",
                   "serve_degradation_transitions_total",
                   'shard_live{shard="0"}',
                   "wal_append_seconds_bucket", "wal_fsync_seconds_bucket",
                   "search_dist_comps_total", "search_hops_total"):
        assert family in prom, family
    snap = json.loads(outp.split("=== metrics (json) ===")[1].strip())
    assert snap["histograms"]["serve_request_latency_seconds"]["count"] == 24
    assert snap["counters"]["search_dist_comps_total"] > 0
    assert any(s["name"] == "serve.request" for s in snap["spans"])
