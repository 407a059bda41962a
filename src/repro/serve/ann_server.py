"""Batched ANN request serving on a δ-EMG / δ-EMQG index.

Request-level batching is how a lock-step accelerator search serves an
online stream: requests accumulate until ``max_batch`` or ``max_wait_s``
elapses, the batch is padded to a fixed bucket size (one trace per bucket),
and per-request results are fanned back out.  Straggler mitigation falls out
of the lock-step formulation — a hard query costs masked iterations instead
of blocking a core.

The server runs the batch-level beam engine — the only engine: ``params.
beam_width`` widens the per-hop frontier (fewer, fatter lock-step iterations
per batch — the QPS/latency knob), and ``backend`` selects the fused
gather+L2 implementation for the distance hot path ("auto" picks the tiled
Pallas kernel on TPU, plain XLA elsewhere).  The resilience layer
(``resilience.py``) wraps this server with admission control, deadlines, and
an error-bounded degradation ladder whose circuit breaker bottoms out at
``(beam, jnp, beam_width=1)`` — greedy best-first on the production engine.

Clocks: every request records two timestamps — ``arrival_t``, the *logical*
arrival time (caller-supplied when replaying a trace, else the submit
instant), and ``wall_t``, the **monotonic** submit time
(``time.perf_counter`` via ``obs.Timer``).  All latency accounting is
two-point monotonic arithmetic (submit → completion); logical arrivals only
order the replay.  The stepping wall clock is banned from this package (CI
grep-lint rejects any ``time.<wall-clock>()`` call): it steps under NTP, and
the seed's wall-clock subtraction could report negative latencies after a
slew.

Observability: pass ``metrics=`` (an ``obs.MetricsRegistry``) and/or
``tracer=`` (an ``obs.Tracer``) to get the standard serve taxonomy —
request-latency / queue-wait / batch-execute histograms, per-status response
counters, batch-aggregated device counters (``n_dist_comps``/``n_hops``/…,
the Exp-5 metrics at serve time) — and per-request spans (``serve.request``
with a ``serve.queue_wait`` child) linked to per-batch spans
(``serve.batch`` → ``serve.batch_form`` / ``serve.device_execute`` /
``serve.merge``; ``serve.device_execute`` → ``serve.put`` / ``serve.launch``
/ ``serve.fetch``).  Both default to ``None`` = zero overhead, and enabling
them cannot change results (pinned bit-identical in ``tests/test_obs.py``).
Each batch's result comes back to the host in one read (``serve.fetch``),
which the counters then aggregate without touching the device again.

Single-process implementation (threads would add nothing in a test
container); the ``submit_many`` / ``drain`` pair models the arrival loop so
benchmarks can replay request traces with arrival timestamps.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    EMQGIndex,
    GraphIndex,
    SearchParams,
    probing_search,
    search,
)
from repro.obs import (
    DEFAULT_WORK_BUCKETS,
    MetricsRegistry,
    Timer,
    Tracer,
    record_search_result,
)


@dataclasses.dataclass
class ServeStats:
    """Serve-loop counters.  The resilience counters (``n_shed`` onward) stay
    zero under the plain ``AnnServer``; ``ResilientAnnServer`` drives them."""

    n_requests: int = 0
    n_batches: int = 0
    total_latency_s: float = 0.0
    max_latency_s: float = 0.0
    # -- resilience counters -------------------------------------------------
    n_rejected: int = 0          # failed per-request validation (shape/NaN/…)
    n_shed: int = 0              # refused by admission control (queue full)
    n_degraded: int = 0          # served at a ladder rung below full quality
    n_retried: int = 0           # search attempts retried after a fault
    n_fallback: int = 0          # circuit-breaker tier switches
    n_deadline_missed: int = 0   # completed after their deadline
    n_failed: int = 0            # exhausted every tier/retry; error response

    @property
    def mean_latency_s(self) -> float:
        return self.total_latency_s / max(self.n_requests, 1)


@dataclasses.dataclass
class _Request:
    """A queued request: logical arrival (trace clock) + monotonic submit."""

    arrival_t: float
    wall_t: float
    query: np.ndarray
    seq: int


class AnnServer:
    def __init__(self, index: GraphIndex | EMQGIndex, params: SearchParams,
                 max_batch: int = 64, buckets: tuple[int, ...] = (8, 32, 64),
                 engine: str = "beam", backend: str = "auto",
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        if engine != "beam":
            raise ValueError(f"unknown engine: {engine!r}")
        self.index = index
        self.params = params
        self.max_batch = max_batch
        self.buckets = tuple(sorted(set(b for b in buckets if b <= max_batch))) \
            or (max_batch,)
        self.quantized = isinstance(index, EMQGIndex)
        self.engine = engine
        self.backend = backend
        self.metrics = metrics
        self.tracer = tracer
        self._queue: list[_Request] = []
        self._seq = 0
        self.stats = ServeStats()

    # the result fields a batch's answers read
    ANSWER_FIELDS = ("ids", "dists")

    def _search(self, queries: jnp.ndarray,
                params: Optional[SearchParams] = None,
                engine: Optional[str] = None,
                backend: Optional[str] = None):
        """Run one batch through the beam engine.  The overrides are the seam
        the resilience layer steers (ladder params, breaker tier) and the
        fault harness wraps; ``engine`` stays a parameter so breaker tiers
        remain addressable (the sharded subclass adds its own tiers)."""
        fn, args, kw = self._program(queries, params, engine, backend)
        return fn(*args, **kw)

    def _execute(self, qs: np.ndarray, **overrides):
        """One padded batch on the device: the queries in (``serve.put``),
        the search call up to its return (``serve.launch``) and one blocking
        read of the result (``serve.fetch``): the whole result where metrics
        or spans observe it, else only ``ANSWER_FIELDS``.  Returns the result
        with host arrays (unread fields ``None``); ``overrides`` go to
        ``_search``."""
        with self._span("serve.put"):
            q = jnp.asarray(qs)
        with self._span("serve.launch"):
            res = self._search(q, **overrides)
        with self._span("serve.fetch"):
            if self.metrics is None and self.tracer is None:
                res = dataclasses.replace(res, **{
                    f.name: None for f in dataclasses.fields(res)
                    if f.name not in self.ANSWER_FIELDS})
            return jax.device_get(res)

    def _program(self, queries, params=None, engine=None, backend=None):
        """The jitted program one batch runs, with its arguments."""
        params = params if params is not None else self.params
        engine = engine if engine is not None else self.engine
        backend = backend if backend is not None else self.backend
        if engine != "beam":
            raise ValueError(f"unknown engine: {engine!r}")
        fn = probing_search if self.quantized else search
        return fn, (self.index, queries, params), {"backend": backend}

    def compile(self, queries, params: Optional[SearchParams] = None,
                engine: Optional[str] = None, backend: Optional[str] = None):
        """Lower and compile the batch program for ``queries``' shape without
        running it; returns the ``jax.stages.Compiled`` (its ``as_text()``
        shows which kernels the device runs).  The compiled program is
        cached, so the next batch of that shape runs it without compiling."""
        fn, args, kw = self._program(queries, params, engine, backend)
        return fn.lower(*args, **kw).compile()

    # -- observability seams -------------------------------------------------
    def _span(self, name: str, parent=None, **attrs):
        """``with`` a tracer span (child of ``parent``, else of the span
        open around it); nothing without a tracer."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, parent=parent, **attrs)

    def _obs_batch(self, n_live: int, res, exec_s: float) -> None:
        """Batch-level metrics: execute-time histogram, batch size, and the
        device-side work counters aggregated host-side (Exp-5 at serve
        time).  ``n_live`` excludes pad rows from the aggregation."""
        if self.metrics is None:
            return
        self.metrics.histogram("serve_batch_execute_seconds").observe(exec_s)
        self.metrics.histogram("serve_batch_size",
                               buckets=DEFAULT_WORK_BUCKETS).observe(n_live)
        if res is not None:
            record_search_result(self.metrics, res, n_live=n_live)

    def _obs_response(self, req: _Request, dispatch_t: float, done_t: float,
                      status: str, batch_span=None) -> None:
        """Per-request metrics + retroactive request/queue-wait spans."""
        if self.metrics is not None:
            self.metrics.counter("serve_responses_total",
                                 {"status": status}).inc()
            if status in ("ok", "failed"):
                self.metrics.histogram(
                    "serve_request_latency_seconds").observe(
                        done_t - req.wall_t)
                self.metrics.histogram("serve_queue_wait_seconds").observe(
                    max(dispatch_t - req.wall_t, 0.0))
        if self.tracer is not None:
            rspan = self.tracer.start_span(
                "serve.request", start=req.wall_t, seq=req.seq, status=status,
                batch=None if batch_span is None else batch_span.span_id)
            qspan = self.tracer.start_span("serve.queue_wait", parent=rspan,
                                           start=req.wall_t)
            self.tracer.end_span(qspan, end=dispatch_t)
            self.tracer.end_span(rspan, end=done_t)

    # -- request path -------------------------------------------------------
    def submit(self, query: np.ndarray, arrival_t: Optional[float] = None):
        wall = Timer.now()
        self._queue.append(_Request(
            arrival_t=arrival_t if arrival_t is not None else wall,
            wall_t=wall, query=np.asarray(query, np.float32), seq=self._seq))
        self._seq += 1

    def submit_many(self, queries: np.ndarray, arrival_ts=None):
        for i, q in enumerate(queries):
            self.submit(q, None if arrival_ts is None else float(arrival_ts[i]))

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        # n exceeds every bucket (max_batch > largest bucket): serve unpadded
        # rather than computing a negative pad.
        return n

    def drain(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Serve everything queued; returns [(ids, dists)] per request in
        submission order."""
        out = []
        tr = self.tracer
        while self._queue:
            take = self._queue[: self.max_batch]
            self._queue = self._queue[self.max_batch:]
            bspan = tr.start_span("serve.batch") if tr else None
            fspan = tr.start_span("serve.batch_form", parent=bspan) \
                if tr else None
            qs = np.stack([r.query for r in take])
            bucket = self._bucket(len(take))
            pad = bucket - len(take)
            if pad:
                qs = np.concatenate([qs, np.repeat(qs[-1:], pad, axis=0)])
            if tr:
                tr.end_span(fspan, size=len(take), bucket=bucket)
            with self._span("serve.device_execute", parent=bspan,
                            backend=self.backend):
                t0 = Timer.now()
                res = self._execute(qs)
                t1 = Timer.now()
            ids, dists = res.ids, res.dists
            self._obs_batch(len(take), res, t1 - t0)
            mspan = tr.start_span("serve.merge", parent=bspan) if tr else None
            for i, req in enumerate(take):
                out.append((ids[i], dists[i]))
                lat = t1 - req.wall_t
                self.stats.n_requests += 1
                self.stats.total_latency_s += lat
                self.stats.max_latency_s = max(self.stats.max_latency_s, lat)
                self._obs_response(req, t0, t1, "ok", batch_span=bspan)
            if tr:
                tr.end_span(mspan)
                tr.end_span(bspan, size=len(take), iters=batch_iters(res))
            self.stats.n_batches += 1
        return out


def batch_iters(res) -> Optional[int]:
    """A batch's lock-step iteration count: the largest ``n_iters`` over its
    rows (``None`` where the result does not count them)."""
    return None if res.n_iters is None else int(np.max(res.n_iters))
