#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same index and server offered
several fixed rates in turn, one window each, in one process.

    python3 bench/knee.py --workload sift-emg.online --seed 7 \\
        --seconds 30 --rates 400,500,600,700 [--rehearse]

For each rate it prints the 50th and 99th percentile latency (from each
request's due time), the rate completed, the backlog (requests due and not
yet answered) at the middle and at the close of the window, how long the
backlog took to drain after the close, and how late the generator woke.
A rate is sustained when the backlog does not grow through the window.
The script writes nothing: the chosen rate, about four fifths of the
highest sustained one, goes into the mix's file by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    rates = [float(r) for r in args.rates.split(",")]

    import jax
    import numpy as np

    from harness import data, traffic
    from harness.spec import Cell
    from repro.launch.cache import use_compile_cache

    if not args.rehearse and jax.default_backend() != "tpu":
        print("knee: JAX backend is not a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = Cell(args.workload, rehearse=args.rehearse)
    cfg, mix = cell.config, cell.traffic
    if mix["loop"] != "open":
        print(f"knee: {cell.name} is not an open-loop cell", file=sys.stderr)
        return 2
    d = dict(cfg["data"])
    n_q = d.pop("queries")
    corpus, queries = data.sift_like(cfg["n"], n_q, dim=cfg["dim"], **d)
    system = cell.system()
    t0 = time.perf_counter()
    index = system.build(corpus, cfg, cfg["data"]["corpus_seed"])
    jax.block_until_ready(index)
    print(f"knee: build {time.perf_counter() - t0:.3f} s", flush=True)
    srv = system.server(index, cfg, mix["max_batch"],
                        mix.get("buckets", [mix["max_batch"]]))
    srv.submit_many(queries[np.arange(mix["max_batch"]) % n_q])
    srv.drain()

    def serve(rows):
        srv.submit_many(rows)
        return srv.drain()

    rows = []
    for i, rate in enumerate(rates):
        rng = np.random.default_rng([args.seed, 2, i])
        sched = traffic.open_schedule(dict(mix, rate_qps=rate),
                                      args.seconds, n_q, rng)
        n0 = srv.stats.n_batches
        s = traffic.open_loop(serve, queries, sched, args.seconds,
                              mix["max_batch"])
        lat = s.done - s.due
        late = s.lateness if s.lateness.size else np.zeros(1)
        row = {
            "rate_qps": rate,
            "completed_qps": float((s.done <= args.seconds).sum()
                                   / args.seconds),
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "backlog_mid": traffic.backlog(s, args.seconds / 2),
            "backlog_close": traffic.backlog(s, args.seconds),
            "drain_s": float(s.done.max() - args.seconds),
            "batch_rows_mean": s.due.size / max(srv.stats.n_batches - n0, 1),
            "late_p99_ms": 1e3 * float(np.percentile(late, 99)),
            "late_max_ms": 1e3 * float(late.max()),
        }
        rows.append(row)
        print("knee: " + json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "seconds": args.seconds,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
