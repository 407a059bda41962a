"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the metrics.

Set-up makes the corpus and the query set, draws the order of the queries
and the arrivals from the seed, builds the index
(timed to ``block_until_ready``: ``build_s``), and serves one batch of the
window's shape so that every program the window runs is compiled (or
loaded from the persistent cache) before it opens.  ``setup_s`` runs from
the start of the process to the opening of the window.

The window drives the server with the cell's mix and the profiler off;
``--trace 1`` runs the same window with the program's spans and counters
and the profiler on, for the per-layer metrics.  After the window the peak
device memory is read, and every answer is judged against the float64
reference (``compare.py``).
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

from . import compare, data, peaks, reference, trace, traffic
from .spec import Cell

SEED_MOD = 2**31 - 1      # JAX keys and the program's seeds take 31 bits


class CompileCounter:
    """Backend compiles in this process, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.n = 0

        def on_event(event, duration_secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def make_inputs(cell: Cell, seed: int, seconds: float):
    """The configuration's corpus and fixed query set; the order of the
    queries and the arrivals from the seed."""
    cfg, mix = cell.config, cell.traffic
    d = dict(cfg["data"])
    n_q = d.pop("queries")
    corpus, queries = data.sift_like(cfg["n"], n_q, dim=cfg["dim"], **d)
    rng = np.random.default_rng([seed, 1])
    sched = traffic.open_schedule(mix, seconds, n_q, rng) \
        if mix["loop"] == "open" else None
    return corpus, queries, sched, rng


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float) -> dict:
    import jax

    cfg, mix = cell.config, cell.traffic
    system = cell.system()
    registry = tracer = None
    if traced:
        from repro.obs import MetricsRegistry, Tracer

        registry, tracer = MetricsRegistry(), Tracer(max_spans=1 << 20)

    corpus, queries, sched, rng = make_inputs(cell, seed, seconds)
    t0 = time.perf_counter()
    index = system.build(corpus, cfg, cfg["data"]["corpus_seed"] % SEED_MOD,
                         metrics=registry)
    jax.block_until_ready(index)
    build_s = time.perf_counter() - t0

    loop_open = mix["loop"] == "open"
    batch = mix["max_batch"] if loop_open else mix["batch"]
    buckets = mix.get("buckets", [batch])
    warm_srv = system.server(index, cfg, batch, buckets)
    warm_srv.submit_many(queries[np.arange(batch) % queries.shape[0]])
    warm_srv.drain()

    srv = system.server(index, cfg, batch, buckets, metrics=registry,
                        tracer=tracer)

    def serve(rows):
        srv.submit_many(rows)
        return srv.drain()

    compiles = CompileCounter()
    trace_dir = os.path.join(cell.root, ".bench_out",
                             f"trace-{cell.name}-{seed}")
    gc.collect()
    setup_s = time.perf_counter() - t_start
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        if loop_open:
            served = traffic.open_loop(serve, queries, sched, seconds,
                                       batch)
        else:
            served = traffic.closed_loop(serve, queries, mix, seconds, rng)
    finally:
        if traced:
            jax.profiler.stop_trace()
    if compiles.n:
        raise RuntimeError(f"{compiles.n} programs compiled inside the "
                           "measured window")
    device = device_info(cell.chips)

    red = None
    if traced and device["platform"] == "tpu":
        red = trace.reduce(trace.load(trace_dir))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
    del srv, warm_srv, index

    n_due = served.n_due
    verdict = compare.judge(corpus, queries, served.qidx, served.ids,
                            served.dists, cfg["k"], cfg["limits"], n_due)

    ctx = SimpleNamespace(
        cell=cell, served=served, registry=registry, tracer=tracer,
        trace=red, n_answers=served.qidx.size, device=device,
        peaks=peaks.peaks(device["kind"]) if red is not None else None)
    values = {}
    if traced:
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = (v, m["unit"])
    else:
        e2e = {"setup_s": setup_s, "build_s": build_s,
               "recall_at_10": 100.0 * verdict["recall"]}
        if loop_open:
            lat = served.done - served.due
            e2e["p99_ms"] = 1e3 * float(np.percentile(lat, 99))
        else:
            e2e["queries_per_s"] = served.qidx.size / served.window_s
        for m in cell.end_to_end:
            values[m["name"]] = (e2e[m["name"]], m["unit"])

    result = {
        "correct": verdict["correct"],
        "attempted": int(n_due),
        "failed": int(verdict["failed"]),
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in values.items()},
        "device": device,
    }
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = verdict["checks"]
    return result


def control(cell: Cell, seed: int, seconds: float) -> dict:
    """The control: the reference computed in bfloat16 answers the queries
    a run of ``seconds`` would send (an open loop's whole schedule; a
    closed loop's whole query set), and the run's comparison judges it."""
    corpus, queries, sched, _ = make_inputs(cell, seed, seconds)
    qidx = sched.qidx if sched is not None else np.arange(queries.shape[0])
    k = cell.config["k"]
    ids, dists = reference.control_answers(corpus, queries[qidx], k)
    return compare.judge(corpus, queries, qidx, ids, dists, k,
                         cell.config["limits"], qidx.size)


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The result as the last line of standard output; each number
    compared, beside its limit, as the last lines of standard error."""
    import json

    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
