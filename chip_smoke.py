#!/usr/bin/env python3
"""Bring-up smoke of the δ-EMQG serve path on a TPU.

    python chip_smoke.py                         # one chip
    python chip_smoke.py --four-chips            # sharded serving, four chips
    python chip_smoke.py --rehearse [--four-chips]   # tiny size, on the CPU

One chip runs the paper's SIFT1M deployment (``configs/sift1m.py``) at its
widths — d=128 f32, M=64, k=10, l_max=512, α=1.2, online batch 256 — with
the build's scale cut (n, the build's L and its refinement iterations, each
printed with its reason), through the entry points a user calls:

1. a clustered corpus and its queries, made from ``--seed``;
2. ``build_emqg`` with a fixed δ, each build phase timed;
3. the served program (``probing_search``, backend ``auto``) compiled once,
   and checked to hold the Pallas kernel (``tpu_custom_call``);
4. several batches served through ``AnnServer``, with no compile after
   warm-up;
5. a query sample checked against the float64 oracle: recall@10, the
   returned distances, and the ``(1/δ)`` bound;
6. ``gather_l2_tiled`` against its jnp reference, and the ``kernel_tiled``
   and ``jnp`` backends against each other on one batch at W=1.

``--four-chips`` runs only the sharded path and what it is compared with:
``build_sharded``, placed over a four-device mesh once (each shard on its
own chip), and ``ShardedResilientAnnServer`` with both merges, its ladder
warmed at every rung and stepped down by the whole backlog arriving at
once, checked batch by batch against ``host_reference_merge`` at the
batch's rung and against the oracle sample.

Any failed phase raises, so the exit code is non-zero.  The last line of
standard output, printed only when every phase passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without
``--rehearse`` a machine whose JAX backend is not a TPU exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Sizes.  The full size keeps every width of configs/sift1m.py (d=128 f32,
# M=64, k=10, l_max=512, alpha=1.2, online batch 256); the corpus size n, the
# build's candidate-list size L and its refinement iterations are cut, each
# printed with its reason.
# The rehearsal is a tiny CPU run of the same code.
FULL = dict(n=100_000, n_four=16_384, beam_width=200, iters=2, batch=256,
            n_batches=3, sample=64, block=1024)
REHEARSE = dict(n=3_000, n_four=4_000, beam_width=64, batch=32, n_batches=3,
                sample=32, block=1024, max_degree=16, t=16, iters=2,
                l_max=64, max_hops=512)
DELTA = 0.2          # fixed construction δ: the (1/δ) bound is 5
CUT_REASONS = {
    "n": "a build at n=1M does not fit the smoke's 1200 s; 100,000 is the "
         "floor this smoke is held to",
    "n_four": "four shards are built one after another inside one call "
              "that holds four chips; 4,096 rows per shard keep it short",
    "beam_width": "each node's candidate search runs about L+1 hops over "
                  "an (L+1)-slot buffer, so the build grows as L^2: L=1000 "
                  "costs 25 times L=200, which keeps the n=100k build within "
                  "a few minutes",
    "iters": "each refinement iteration at n=100k, L=200 took 150-310 s on "
             "a TPU v5 lite; two keep the smoke near half of its 1200 s "
             "limit",
}


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


@contextlib.contextmanager
def phase(name: str):
    say(f"phase {name} ...")
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        say(f"phase {name} FAILED after {time.perf_counter() - t0:.3f} s")
        raise
    say(f"phase {name} ok in {time.perf_counter() - t0:.3f} s")


class CompileCounter:
    """Counts backend compiles in this process (JAX monitoring events)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0

        def on_event(event, duration_secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def configure(args) -> dict:
    """Params from configs/sift1m.py at full width; tiny for --rehearse."""
    import dataclasses

    from repro.configs import sift1m

    size = REHEARSE if args.rehearse else FULL
    bp = dataclasses.replace(sift1m.BUILD, delta=DELTA, block=size["block"],
                             beam_width=size["beam_width"],
                             iters=size["iters"], seed=args.seed)
    sp = sift1m.SEARCH
    if args.rehearse:
        bp = dataclasses.replace(bp, max_degree=size["max_degree"],
                                 t=size["t"])
        sp = dataclasses.replace(sp, l_max=size["l_max"],
                                 max_hops=size["max_hops"])
    n = size["n_four"] if args.four_chips else size["n"]
    say(f"config sift1m: n={n} (config {sift1m.N}) d={sift1m.DIM} "
        f"M={bp.max_degree} L={bp.beam_width} t={bp.t} iters={bp.iters} "
        f"k={sp.k} l_max={sp.l_max} alpha={sp.alpha} "
        f"W={sp.beam_width} batch={size['batch']}")
    if args.rehearse:
        say("cut: rehearsal — tiny corpus and graph on the CPU; every number "
            "below is a control-flow check, not a device measurement")
    else:
        key = "n_four" if args.four_chips else "n"
        say(f"cut: n {sift1m.N} -> {n}: {CUT_REASONS[key]}")
        say(f"cut: build L (BuildParams.beam_width) "
            f"{sift1m.BUILD.beam_width} -> {bp.beam_width}: "
            f"{CUT_REASONS['beam_width']}")
        say(f"cut: build refinement iterations (BuildParams.iters) "
            f"{sift1m.BUILD.iters} -> {bp.iters}: {CUT_REASONS['iters']}")
    say(f"change: fixed delta={DELTA} instead of the adaptive delta_t rule, "
        f"so the (1/delta) bound is finite ({1 / DELTA:g})")
    say(f"build block={bp.block} nodes per device batch (config default "
        f"{sift1m.BUILD.block})")
    return dict(size=size, bp=bp, sp=sp, n=n, d=sift1m.DIM)


def make_data(n: int, d: int, n_queries: int, seed: int):
    """Corpus and in-distribution queries from one clustered draw."""
    from repro.data import clustered_vectors

    x = clustered_vectors(n + n_queries, d, 64, seed=seed)
    return x[:n], x[n:]


def check_against_oracle(base, queries, ids, dists, k: int, what: str):
    import numpy as np

    from repro.testing.oracle import check_delta_bound, exact_knn, recall_at_k

    orc_d, orc_i = exact_knn(base, queries, k)
    rec = recall_at_k(ids, orc_i)
    assert (ids >= 0).all() and (ids < base.shape[0]).all(), \
        f"{what}: invalid ids returned"
    true = np.linalg.norm(base[ids].astype(np.float64)
                          - queries[:, None, :].astype(np.float64), axis=-1)
    np.testing.assert_allclose(dists, true, rtol=1e-4, atol=1e-4,
                               err_msg=f"{what}: distances are not the "
                                       "true distances of the returned ids")
    bound = check_delta_bound(dists, orc_d, DELTA, alpha=1.0)
    say(f"{what}: recall@{k}={rec:.4f} over {len(queries)} oracle queries; "
        f"check_delta_bound(delta={DELTA}, alpha=1.0) -> {bound}")
    assert bound is None, bound
    return rec


def one_chip(args, cfg, on_tpu: bool) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build_emqg, probing_search
    from repro.core.verify import audit
    from repro.kernels.l2dist import ops as l2ops
    from repro.kernels.l2dist import ref as l2ref
    from repro.serve import AnnServer

    size, bp, sp, n, d = (cfg[k] for k in ("size", "bp", "sp", "n", "d"))
    B = size["batch"]
    compiles = CompileCounter()
    dev = jax.devices()[0]

    with phase("data"):
        base, queries = make_data(n, d, B * size["n_batches"], args.seed)
        say(f"corpus {base.shape} {base.dtype}, queries {queries.shape}")

    with phase("build"):
        t0 = time.perf_counter()
        idx = build_emqg(base, bp, verbose=True)
        jax.block_until_ready(idx)
        deg = np.asarray(idx.graph.degrees())
        say(f"build total {time.perf_counter() - t0:.3f} s; mean degree "
            f"{deg.mean():.2f}, min {deg.min()}; memory {memory(dev)}")
        rep = audit(idx.graph, sample=0, check_monotone=False)
        say(f"graph audit: {rep.metrics['n_unreachable_live']} of {n} nodes "
            f"unreachable from the medoid; {len(rep.violations)} invariant "
            f"violations")

    srv = AnnServer(idx, sp, max_batch=B, buckets=(B,))
    with phase("compile"):
        t0 = time.perf_counter()
        compiled = srv.compile(jnp.asarray(queries[:B]))
        hlo = compiled.as_text()
        kernel_in = "tpu_custom_call" in hlo
        say(f"served program compiled in {time.perf_counter() - t0:.3f} s; "
            f"backend auto on {jax.default_backend()}; tpu_custom_call in "
            f"compiled HLO: {kernel_in}")
        if on_tpu:
            assert kernel_in, "served program holds no Pallas kernel"

    with phase("serve"):
        t0 = time.perf_counter()
        srv.submit_many(queries[:B])
        out = srv.drain()                   # warm-up batch
        n0 = compiles.n
        srv.submit_many(queries[B:])
        out += srv.drain()
        wall = time.perf_counter() - t0
        ids = np.stack([r[0] for r in out])
        dists = np.stack([r[1] for r in out])
        say(f"served {srv.stats.n_requests} queries in "
            f"{srv.stats.n_batches} batches of {B} ({wall:.3f} s host wall "
            f"clock, {jax.default_backend()}); compiles after warm-up: "
            f"{compiles.n - n0}")
        assert len(out) == len(queries) and srv.stats.n_batches == \
            size["n_batches"]
        assert compiles.n == n0, "the serve loop recompiled after warm-up"

    with phase("oracle"):
        s = size["sample"]
        check_against_oracle(base, queries[:s], ids[:s], dists[:s], sp.k,
                             "served")

    with phase("kernel"):
        rng = np.random.default_rng(args.seed)
        kid = jnp.asarray(rng.integers(0, n, (B, bp.max_degree)), jnp.int32)
        qb = jnp.asarray(queries[:B])
        vec = idx.graph.vectors
        got = np.asarray(l2ops.gather_l2_tiled(vec, kid, qb))
        ref = np.asarray(l2ref.gather_l2_ref(vec, kid, qb))
        rel = float(np.max(np.abs(got - ref) / np.maximum(ref, 1e-30)))
        say(f"gather_l2_tiled vs ref.py on [{B}, {bp.max_degree}] ids: max "
            f"relative difference {rel:.3e}")
        assert rel <= 1e-5, rel
        p1 = dataclasses.replace(sp, beam_width=1)
        rk = probing_search(idx, qb, p1, backend="kernel_tiled")
        rj = probing_search(idx, qb, p1, backend="jnp")
        ik, ij = np.asarray(rk.ids), np.asarray(rj.ids)
        dk, dj = np.asarray(rk.dists), np.asarray(rj.dists)
        n_diff = int((ik != ij).any(axis=1).sum())
        ddiff = float(np.max(np.abs(dk - dj)))
        say(f"kernel_tiled vs jnp, W=1, one batch of {B}: queries with "
            f"different ids {n_diff}; max |dist difference| {ddiff:.3e}")
        assert n_diff == 0, f"{n_diff} queries differ between backends"
        np.testing.assert_allclose(dk, dj, rtol=1e-5, atol=1e-5)

    say(f"memory {memory(dev)}")


def four_chips(args, cfg, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import (build_sharded, host_reference_merge,
                                        place_sharded)
    from repro.serve import ShardedResilientAnnServer

    size, bp, sp, n, d = (cfg[k] for k in ("size", "bp", "sp", "n", "d"))
    B, S, nb = size["batch"], 4, size["n_batches"]
    devs = jax.devices()
    assert len(devs) == S, f"--four-chips needs 4 devices, found {len(devs)}"
    mesh = Mesh(np.array(devs), ("data",))
    compiles = CompileCounter()

    with phase("data"):
        base, queries = make_data(n, d, B * nb, args.seed)
        say(f"corpus {base.shape}, queries {queries.shape}, S={S} shards of "
            f"{-(-n // S)}")

    with phase("build_sharded"):
        t0 = time.perf_counter()
        sidx = build_sharded(base, S, bp, quantized=True, seed=args.seed)
        jax.block_until_ready(sidx)
        t1 = time.perf_counter()
        sidx = place_sharded(sidx, mesh)
        jax.block_until_ready(sidx)
        say(f"sharded build {t1 - t0:.3f} s, placement "
            f"{time.perf_counter() - t1:.3f} s")
        want = NamedSharding(mesh, P("data"))
        for leaf in jax.tree.leaves(sidx):
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim), \
                leaf.sharding
            rows = sorted(s.index[0].start for s in leaf.addressable_shards)
            assert rows == list(range(S)), rows
        for dv in devs:
            say(f"device {dv.id}: {memory(dv)}")

    # The whole backlog is submitted at once, so the degradation ladder
    # steps down a rung per batch, as it does under a real overload; every
    # rung's program is compiled by warm() before the first batch.
    for merge in ("all_gather", "ring"):
        with phase(f"serve_{merge}"):
            srv = ShardedResilientAnnServer(
                sidx, sp, mesh, quantized=True, merge=merge, max_batch=B,
                buckets=(B,))
            t0 = time.perf_counter()
            n_prog = srv.warm()
            say(f"{merge}: {n_prog} programs (one per ladder rung) compiled "
                f"in {time.perf_counter() - t0:.3f} s")
            n0 = compiles.n
            srv.submit_many(queries)
            rs = srv.drain()
            rungs = [rs[b * B].rung for b in range(nb)]
            say(f"{merge}: {len(rs)} responses in {srv.stats.n_batches} "
                f"batches at rungs {rungs}; compiles while serving "
                f"{compiles.n - n0}; fallbacks {srv.stats.n_fallback}, "
                f"retries {srv.stats.n_retried}")
            assert len(rs) == B * nb and all(r.ok for r in rs), \
                [r.error for r in rs if not r.ok]
            assert {r.tier for r in rs} == {f"sharded/{merge}"}, \
                {r.tier for r in rs}
            assert all({r.rung for r in rs[b * B:(b + 1) * B]} == {rungs[b]}
                       for b in range(nb)), "a batch mixed rungs"
            assert max(rungs) > 0, "the backlog never stepped the ladder"
            assert srv.stats.n_fallback == 0 and srv.stats.n_retried == 0
            assert compiles.n == n0, "sharded serving compiled while serving"
            ids = np.stack([r.ids for r in rs])
            dists = np.stack([r.dists for r in rs])
            for b in range(nb):
                sl = slice(b * B, (b + 1) * B)
                ref_i, ref_d = host_reference_merge(
                    sidx, srv.registry, jnp.asarray(queries[sl]),
                    srv.ladder.params(rungs[b]), quantized=True)
                same = int((ids[sl] == ref_i).all(axis=1).sum())
                say(f"{merge}: batch {b} (rung {rungs[b]}) matches "
                    f"host_reference_merge on {same}/{B} queries; max |dist "
                    f"difference| "
                    f"{float(np.max(np.abs(dists[sl] - ref_d))):.3e}")
                assert same == B
                np.testing.assert_allclose(dists[sl], ref_d, rtol=1e-5,
                                           atol=1e-5)
            s = size["sample"]
            check_against_oracle(base, queries[:s], ids[:s], dists[:s],
                                 sp.k, f"sharded {merge} (rung {rungs[0]})")
    for dv in devs:
        say(f"device {dv.id}: {memory(dv)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip path")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU (reports platform cpu)")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    import jax

    platform = jax.default_backend()
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX backend is {platform!r}, not a TPU; "
              "use --rehearse for a CPU run", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device {device}; jax {jax.__version__}")

    from repro.launch.cache import use_compile_cache

    say(f"compile cache {use_compile_cache()}")
    cfg = configure(args)
    if args.four_chips:
        four_chips(args, cfg, platform == "tpu")
    else:
        one_chip(args, cfg, platform == "tpu")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
