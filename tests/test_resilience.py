"""Resilience layer: admission control, degradation ladder, deadlines,
fault containment (retry / circuit breaker / tier fallback), and the serve
regressions (bucket clamp, clock-consistent latency accounting).

The fault-injection tests carry ``@pytest.mark.faults`` so CI can run the
suite explicitly (and under a pytest-timeout ceiling: an injected hang must
fail fast, not wedge the job)."""

import math
import time

import numpy as np
import pytest

import dataclasses

from repro.core import SearchParams, build_exact, search
from repro.serve import (
    AnnServer,
    CircuitBreaker,
    DegradationLadder,
    ResilienceConfig,
    ResilientAnnServer,
    TierCompileError,
    validate_query,
)
from repro.serve.resilience import default_tiers
from repro.testing import FaultPlan, KernelFault, inject_search_faults

import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(7)
    base = rng.normal(size=(300, 16)).astype(np.float32)
    with pytest.warns(UserWarning):          # degree cap on a dense corpus
        graph = build_exact(base, delta=0.15, max_degree=12)
    queries = rng.normal(size=(64, 16)).astype(np.float32)
    return {"graph": graph, "queries": queries}


PARAMS = SearchParams(k=5, l0=8, l_max=64, alpha=1.4, adaptive=True,
                      max_hops=512, beam_width=4)


def fast_cfg(**kw):
    kw.setdefault("backoff_s", 0.0)
    return ResilienceConfig(**kw)


# ---------------------------------------------------------------------------
# Serve regressions (satellites).
# ---------------------------------------------------------------------------


def test_drain_bucket_clamp_regression(tiny):
    """max_batch above the largest bucket used to compute a negative pad and
    crash np.repeat; the batch must be served unpadded instead."""
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=100, buckets=(8, 32, 64))
    srv.submit_many(np.concatenate([tiny["queries"], tiny["queries"][:36]]))
    out = srv.drain()                       # first take: 100 > largest bucket
    assert len(out) == 100
    assert srv.stats.n_batches == 1


def test_replay_trace_latency_uses_wall_clock(tiny):
    """Synthetic arrival timestamps (trace clock) must not leak into the
    wall-clock latency accounting — the seed mixed the two and reported
    nonsense (≈ wall_time - trace_time) latencies."""
    srv = AnnServer(tiny["graph"], PARAMS, max_batch=32, buckets=(32,))
    # an absurd trace clock: arrivals billions of seconds in the past/future
    srv.submit_many(tiny["queries"][:32],
                    arrival_ts=np.linspace(-2e9, 2e9, 32))
    out = srv.drain()
    assert len(out) == 32
    assert 0.0 <= srv.stats.mean_latency_s < 120.0
    assert 0.0 <= srv.stats.max_latency_s < 120.0


# ---------------------------------------------------------------------------
# Per-request validation.
# ---------------------------------------------------------------------------


def test_validate_query_reasons():
    assert validate_query(np.zeros(16, np.float32), 16) is None
    assert validate_query(np.zeros(16, np.int32), 16) is None  # castable
    assert "dim" in validate_query(np.zeros(7, np.float32), 16)
    assert "rank-1" in validate_query(np.zeros((2, 16), np.float32), 16)
    assert "non-finite" in validate_query(
        np.array([np.nan] * 16, np.float32), 16)
    assert "non-finite" in validate_query(
        np.array([np.inf] + [0.0] * 15, np.float32), 16)
    assert validate_query(["a"] * 16, 16) is not None


def test_nan_query_rejected_per_request_not_per_batch(tiny):
    """One bad query must cost *itself* the response, not its batch."""
    srv = ResilientAnnServer(tiny["graph"], PARAMS, config=fast_cfg(),
                             max_batch=8, buckets=(8,))
    good = tiny["queries"][:6]
    srv.submit(good[0])
    srv.submit(np.array([np.nan] * 16, np.float32))     # NaN
    srv.submit(good[1])
    srv.submit(np.zeros(7, np.float32))                 # wrong dim
    srv.submit(np.array([np.inf] * 16, np.float32))     # Inf
    for q in good[2:]:
        srv.submit(q)
    rs = srv.drain()
    assert len(rs) == 9
    statuses = [r.status for r in rs]
    assert statuses.count("rejected") == 3
    assert statuses.count("ok") == 6
    assert srv.stats.n_rejected == 3 and srv.stats.n_requests == 6
    # the good queries got real results, identical to an unfaulted server
    ref = search(tiny["graph"], jnp.asarray(good), PARAMS)
    ok = [r for r in rs if r.ok]
    for i, r in enumerate(ok):
        assert r.ids.shape == (PARAMS.k,)
        np.testing.assert_array_equal(r.ids, np.asarray(ref.ids)[i])


# ---------------------------------------------------------------------------
# Degradation ladder.
# ---------------------------------------------------------------------------


def test_ladder_rungs_monotone():
    lad = DegradationLadder(PARAMS, delta=0.2, n_rungs=4)
    lmaxs = [lad.params(r).l_max for r in range(4)]
    beams = [lad.params(r).beam_width for r in range(4)]
    alphas = [lad.params(r).alpha for r in range(4)]
    bounds = [lad.delta_bound(r) for r in range(4)]
    assert lmaxs == sorted(lmaxs, reverse=True) and lmaxs[-1] >= PARAMS.k
    assert beams == sorted(beams, reverse=True) and beams[-1] >= 1
    assert alphas == sorted(alphas, reverse=True) and alphas[-1] >= 1.0
    # relaxing α loosens (grows) the reported approximation factor, but it
    # stays finite and never exceeds the pure-monotonicity bound 1/δ
    assert bounds == sorted(bounds)
    assert all(math.isfinite(b) and b <= 1 / 0.2 + 1e-9 for b in bounds)
    # unknown construction δ → honest infinite bound
    assert math.isinf(DegradationLadder(PARAMS, delta=0.0).delta_bound(0))


def test_overload_engages_ladder_with_finite_bounds(tiny):
    """Under injected overload the server keeps accepting and serving, and
    every degraded response reports a finite δ error bound."""
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=fast_cfg(degrade_depth=8, recover_depth=2, n_rungs=4),
        max_batch=8, buckets=(8,))
    reps = np.repeat(tiny["queries"], 2, axis=0)        # 128-deep burst
    srv.submit_many(reps)
    rs = srv.drain()
    assert len(rs) == len(reps)
    assert all(r.ok for r in rs)
    assert srv.stats.n_degraded > 0
    degraded = [r for r in rs if r.rung > 0]
    assert degraded, "overload never engaged the ladder"
    assert all(math.isfinite(r.delta_bound) for r in degraded)
    assert all(r.delta_bound >= 1.0 for r in degraded)
    # degraded responses still return k well-formed neighbors
    for r in degraded[:5]:
        assert r.ids.shape == (PARAMS.k,)
        assert (np.diff(r.dists) >= -1e-5).all()


def test_warm_compiles_every_rung_before_an_overload(tiny):
    """``warm()`` compiles the primary tier at every rung for every bucket,
    so a burst that walks the ladder down compiles nothing while serving."""
    import jax.monitoring

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=fast_cfg(degrade_depth=8, recover_depth=2, n_rungs=3),
        max_batch=8, buckets=(4, 8))
    assert srv.warm() == 2 * 3                  # buckets × rungs
    assert srv.warm() == 0                      # already compiled
    n0 = len(compiles)
    srv.submit_many(tiny["queries"][:36])       # last batch fills bucket 4
    rs = srv.drain()
    assert all(r.ok for r in rs)
    assert {r.rung for r in rs} == {1, 2}
    assert len(compiles) == n0, "serving compiled after warm()"


def test_ladder_recovers_when_queue_drains(tiny):
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=fast_cfg(degrade_depth=8, recover_depth=4, n_rungs=3),
        max_batch=8, buckets=(8,))
    srv.submit_many(np.repeat(tiny["queries"], 2, axis=0))
    srv.drain()
    peak = srv.rung
    assert peak > 0
    for _ in range(peak + 1):                # light traffic → climb back up
        srv.submit_many(tiny["queries"][:2])
        rs = srv.drain()
    assert srv.rung == 0
    assert rs[-1].rung <= 1                  # last light batch near full quality


# ---------------------------------------------------------------------------
# Admission control, deadlines.
# ---------------------------------------------------------------------------


def test_queue_full_sheds_without_exception(tiny):
    srv = ResilientAnnServer(tiny["graph"], PARAMS,
                             config=fast_cfg(max_queue=4),
                             max_batch=8, buckets=(8,))
    terminal = [srv.submit(q) for q in tiny["queries"][:10]]
    assert sum(t is not None and t.status == "shed" for t in terminal) == 6
    rs = srv.drain()
    assert len(rs) == 10                     # one response per submission
    assert sum(r.status == "shed" for r in rs) == 6
    assert sum(r.ok for r in rs) == 4
    assert srv.stats.n_shed == 6
    # responses come back in submission order
    assert [r.seq for r in rs] == sorted(r.seq for r in rs)


def test_expired_deadline_dropped_at_dispatch(tiny):
    srv = ResilientAnnServer(tiny["graph"], PARAMS,
                             config=fast_cfg(deadline_s=0.0),
                             max_batch=8, buckets=(8,))
    srv.submit_many(tiny["queries"][:8])
    time.sleep(0.01)
    rs = srv.drain()
    assert all(r.status == "deadline" for r in rs)
    assert srv.stats.n_deadline_missed == 8
    assert srv.stats.n_requests == 0         # no search budget burned


@pytest.mark.faults
def test_latency_spike_flags_deadline_missed(tiny):
    srv = ResilientAnnServer(tiny["graph"], PARAMS,
                             config=fast_cfg(deadline_s=0.05),
                             max_batch=8, buckets=(8,))
    with inject_search_faults(srv, FaultPlan(latency_s=0.12)):
        srv.submit_many(tiny["queries"][:8])
        rs = srv.drain()
    assert all(r.ok for r in rs)             # still answered …
    assert all(r.deadline_missed for r in rs)  # … but flagged late
    assert srv.stats.n_deadline_missed == 8


# ---------------------------------------------------------------------------
# Fault containment: retry, breaker, tier fallback.
# ---------------------------------------------------------------------------


@pytest.mark.faults
def test_transient_fault_retried_same_tier(tiny):
    srv = ResilientAnnServer(tiny["graph"], PARAMS, config=fast_cfg(),
                             max_batch=8, buckets=(8,))
    with inject_search_faults(srv, FaultPlan(fail_first=1)) as inj:
        srv.submit_many(tiny["queries"][:8])
        rs = srv.drain()
    assert inj.n_failed == 1
    assert all(r.ok for r in rs)
    assert srv.stats.n_retried == 1
    assert srv.stats.n_fallback == 0
    assert all(r.tier.startswith("beam") for r in rs)


@pytest.mark.faults
def test_persistent_kernel_fault_falls_back_to_single_beam(tiny):
    """A fault that kills every wide-beam configuration (e.g. a broken
    multi-row gather kernel) must walk the breaker down to the last-resort
    ``(beam, jnp, W=1)`` tier — greedy best-first on the production engine,
    with results identical to calling it directly, and zero failed
    requests.  There is no tier below it — W=1 on the batch engine is the
    floor of the chain."""
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=fast_cfg(breaker_threshold=2), max_batch=8, buckets=(8,))
    qs = tiny["queries"][:16]
    with inject_search_faults(
            srv, FaultPlan(fail_first=10**6, match_engine="beam",
                           match_min_beam_width=2)) as inj:
        srv.submit_many(qs)
        rs = srv.drain()
    assert inj.n_failed >= 2
    assert all(r.ok for r in rs) and srv.stats.n_failed == 0
    assert srv.stats.n_fallback >= 1
    assert all(r.tier == "beam/jnp/w1" for r in rs)
    ref = search(tiny["graph"], jnp.asarray(qs),
                 dataclasses.replace(srv.ladder.params(srv.rung),
                                     beam_width=1), backend="jnp")
    np.testing.assert_array_equal(
        np.stack([r.ids for r in rs]), np.asarray(ref.ids))


@pytest.mark.faults
def test_breaker_ladder_bottoms_out_at_beam_jnp_w1(tiny):
    """The tier log of a persistent-fault walk must end at the terminal
    ``(beam, jnp, 1)`` tier and never mention any other engine — there is
    no engine below the beam engine to reach for."""
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=fast_cfg(breaker_threshold=2), max_batch=8, buckets=(8,))
    with inject_search_faults(
            srv, FaultPlan(fail_first=10**6, match_engine="beam",
                           match_min_beam_width=2)) as inj:
        srv.submit_many(tiny["queries"][:16])
        rs = srv.drain()
    assert all(r.ok for r in rs)
    assert inj.tier_log[-1] == ("beam", "jnp", 1)
    assert {t[0] for t in inj.tier_log} == {"beam"}
    # the walked ladder is exactly the default chain, in order
    walked = []
    for t in inj.tier_log:
        if t not in walked:
            walked.append(t)
    assert walked == [("beam", "auto", PARAMS.beam_width),
                      ("beam", "jnp", PARAMS.beam_width), ("beam", "jnp", 1)]


@pytest.mark.faults
def test_every_tier_dead_yields_failed_responses_not_a_crash(tiny):
    """Exhausting the whole chain raises cleanly *inside* the containment:
    per-request ``status="failed"``, no crash, and the final attempt was on
    the terminal ``(beam, jnp, 1)`` tier — not some deleted engine."""
    srv = ResilientAnnServer(
        tiny["graph"], PARAMS,
        config=fast_cfg(breaker_threshold=2, max_retries=1),
        max_batch=8, buckets=(8,))
    with inject_search_faults(srv, FaultPlan(fail_first=10**6)) as inj:
        srv.submit_many(tiny["queries"][:8])
        rs = srv.drain()                     # must not raise
    assert all(r.status == "failed" for r in rs)
    assert all("KernelFault" in r.error for r in rs)
    assert srv.stats.n_failed == 8
    assert inj.tier_log[-1] == ("beam", "jnp", 1)
    assert {t[0] for t in inj.tier_log} == {"beam"}


@pytest.mark.faults
def test_tier_that_fails_to_lower_raises_not_falls_back(tiny, monkeypatch):
    """A kernel the device refuses is a program error: the batch must not
    be served from ``(beam, jnp)`` as ``ok``.  The kernel is replaced by one
    that fails while the program is traced and lowered, as Mosaic does for
    a block layout the chip cannot tile."""
    from repro.kernels.l2dist import ops as l2ops

    def refused(*_a, **_kw):
        raise NotImplementedError("Unsupported block layout (injected)")

    monkeypatch.setattr(l2ops, "gather_l2_tiled", refused)
    params = dataclasses.replace(PARAMS, l_max=48)     # a fresh trace
    srv = ResilientAnnServer(tiny["graph"], params, config=fast_cfg(),
                             max_batch=8, buckets=(8,),
                             backend="kernel_tiled")
    srv.submit_many(tiny["queries"][:8])
    with pytest.raises(TierCompileError, match="beam/kernel_tiled"):
        srv.drain()
    assert srv.stats.n_requests == 0 and srv.stats.n_fallback == 0
    # a runtime fault on the same server still walks the breaker
    monkeypatch.undo()
    with inject_search_faults(srv, FaultPlan(fail_first=1)) as inj:
        srv.submit_many(tiny["queries"][:8])
        rs = srv.drain()
    assert inj.n_failed == 1 and all(r.ok for r in rs)


def test_circuit_breaker_half_open_recovery():
    t = [0.0]
    br = CircuitBreaker([("beam", "auto"), ("beam", "jnp")],
                        threshold=2, cooldown_s=10.0, clock=lambda: t[0])
    assert br.current()[0] == 0
    br.record_failure(0)
    assert br.current()[0] == 0              # below threshold: still closed
    br.record_failure(0)
    assert br.current()[0] == 1              # open → fallback tier
    t[0] = 5.0
    assert br.current()[0] == 1              # still cooling down
    t[0] = 11.0
    assert br.current()[0] == 0              # half-open: probe the primary
    br.record_failure(0)                     # probe fails → re-open
    assert br.current()[0] == 1
    t[0] = 25.0
    br.record_success(0)                     # second probe succeeds → closed
    assert br.current()[0] == 0
    assert br.tiers[0].failures == 0


def test_default_tiers_chain():
    """The chain always bottoms out at ``(beam, jnp, 1)`` — greedy
    best-first on the batch engine is the terminal tier for any starting
    engine/backend, and no deleted engine name can reappear."""
    assert default_tiers("beam", "auto") == \
        [("beam", "auto", None), ("beam", "jnp", None), ("beam", "jnp", 1)]
    assert default_tiers("beam", "jnp") == \
        [("beam", "jnp", None), ("beam", "jnp", 1)]
    for engine in ("beam", "probing"):
        for backend in ("auto", "jnp", "kernel", "kernel_tiled"):
            chain = default_tiers(engine, backend)
            assert chain[-1] == ("beam", "jnp", 1)
            assert len(chain) == len(set(chain))      # no duplicate tiers
            assert all(t[0] in ("beam", "probing") for t in chain)
