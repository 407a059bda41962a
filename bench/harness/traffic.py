"""One general traffic generator and the two loops that drive a server.

A mix is a data file of parameters (``bench/traffic/<mix>.json``).  Every
request carries a query of the configuration's fixed query set; the run's
seed only orders the queries and draws the arrivals, so every seed sends
the same work:

* ``"loop": "open"`` — independent users.  ``rate_qps`` arrivals a second
  on average over the window, drawn as a Poisson process conditioned on
  its count: exactly ``round(rate_qps · seconds)`` arrivals, uniform over
  the window.  The requests take the query set in an order drawn from the
  seed, cycling through the whole set before any query repeats.  Up to
  ``max_batch`` waiting requests go to the server at a time.
* ``"loop": "closed"`` — one client sending ``batch`` queries at a time,
  the next batch when the last is answered.  Batch ``i`` holds the query
  set's rows ``i·batch`` to ``(i+1)·batch − 1``, wrapping round its end, in
  an order within the batch drawn from the seed: a bulk job over a fixed
  file.  The window ends at the end of the last whole batch that started
  before ``seconds`` had passed.

Open-loop requests are timed from when they were due, not from when the
loop got round to them, and every request due in the window is waited
for, also after the window has closed.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import numpy as np
from jax.profiler import TraceAnnotation


@dataclass
class Schedule:
    due: np.ndarray          # f64[N] seconds after the window opens
    qidx: np.ndarray         # int64[N] query row of each request


def open_schedule(mix: dict, seconds: float, n_queries: int,
                  rng: np.random.Generator) -> Schedule:
    n = int(round(mix["rate_qps"] * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    cycles = -(-n // n_queries)
    qidx = np.concatenate([rng.permutation(n_queries)
                           for _ in range(cycles)])[:n]
    return Schedule(due, qidx)


@dataclass
class Served:
    n_due: int               # requests due (open) or sent (closed)
    qidx: np.ndarray         # query row of each answered request
    ids: np.ndarray          # int[A, k]
    dists: np.ndarray        # f32[A, k]
    due: np.ndarray | None   # open loop: due time of every request
    done: np.ndarray         # open loop: answer time of every request (the
    #                          loop's end if none came); closed: of each answer
    window_s: float
    lateness: np.ndarray     # open loop: seconds the generator woke late


def open_loop(serve, queries: np.ndarray, sched: Schedule, seconds: float,
              max_batch: int, clock=time.perf_counter) -> Served:
    """Offer ``sched`` to ``serve(rows) -> [(ids, dists)]``."""
    n = sched.due.size
    got, ids, dists = [], [], []
    done = np.full(n, np.inf)
    pending, late = deque(), []
    i = served = 0
    t0 = clock()
    with TraceAnnotation("window"):
        while served < n:
            now = clock() - t0
            while i < n and sched.due[i] <= now:
                pending.append(i)
                i += 1
            if not pending:
                with TraceAnnotation("generator_wait"):
                    wait = sched.due[i] - (clock() - t0)
                    if wait > 0:
                        time.sleep(wait)
                    late.append(clock() - t0 - sched.due[i])
                continue
            with TraceAnnotation("batch_form"):
                take = [pending.popleft()
                        for _ in range(min(max_batch, len(pending)))]
                rows = queries[sched.qidx[take]]
            with TraceAnnotation("device_execute"):
                out = serve(rows)
            t = clock() - t0
            with TraceAnnotation("fan_out"):
                for j, (a, b) in zip(take, out):
                    got.append(j)
                    ids.append(a)
                    dists.append(b)
                    done[j] = t
                served += len(take)
        done[np.isinf(done)] = clock() - t0     # never answered
    return Served(n, sched.qidx[got], _stack(ids), _stack(dists),
                  sched.due, done, seconds, np.asarray(late))


def closed_loop(serve, queries: np.ndarray, mix: dict, seconds: float,
                rng: np.random.Generator, clock=time.perf_counter) -> Served:
    b, n_q = mix["batch"], queries.shape[0]
    qidx, ids, dists, done = [], [], [], []
    i = sent = 0
    t0 = clock()
    with TraceAnnotation("window"):
        while True:
            with TraceAnnotation("batch_form"):
                rows_idx = (i * b + rng.permutation(b)) % n_q
                rows = queries[rows_idx]
            with TraceAnnotation("device_execute"):
                out = serve(rows)
            t = clock() - t0
            with TraceAnnotation("fan_out"):
                sent += b
                qidx.append(rows_idx[:len(out)])
                ids += [a for a, _ in out]
                dists += [d for _, d in out]
                done.append(np.full(len(out), t))
            i += 1
            if t >= seconds:
                break
    return Served(sent, np.concatenate(qidx), _stack(ids), _stack(dists),
                  None, np.concatenate(done), t, np.zeros(0))


def _stack(rows):
    return np.stack(rows) if rows else np.zeros((0, 0))


def backlog(served: Served, t: float) -> int:
    """Requests due by ``t`` and not answered by ``t`` (open loop)."""
    return int((served.due <= t).sum() - (served.done <= t).sum())
