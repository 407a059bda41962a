"""End-to-end ANN *serving* driver (the paper's system in its deployment
shape): δ-EMQG + RaBitQ + probing search behind a batched request queue,
then the sharded variant of the same index over every visible device, in
the same process.

    PYTHONPATH=src python examples/vector_serve.py

On the CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` gives the
sharded part four devices; on a TPU host it shards over the chips.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import BuildParams, SearchParams, build_emqg
from repro.core.distances import brute_force_knn
from repro.core.distributed import (build_sharded, make_sharded_search,
                                    place_sharded)
from repro.data import clustered_vectors
from repro.serve import AnnServer


def main():
    n, dim, k = 4000, 48, 10
    base = clustered_vectors(n, dim, 48, seed=0)
    queries = clustered_vectors(300, dim, 48, seed=1)
    gt_d, gt_i = brute_force_knn(queries, base, k)
    dev = jax.devices()[0]
    where = f"{dev.platform} ({dev.device_kind})"

    print("building δ-EMQG (RaBitQ codes + degree-aligned graph)…")
    bp = BuildParams(max_degree=24, beam_width=64, t=32, iters=2, block=1024,
                     align_degree=True)
    t0 = time.perf_counter()
    idx = build_emqg(base, bp)
    print(f"  built in {time.perf_counter() - t0:.1f}s on {where}; code "
          f"compression = {base.nbytes / (np.asarray(idx.codes.codes).nbytes):.0f}×")

    params = SearchParams(k=k, l0=k, l_max=192, alpha=1.3, adaptive=True,
                          max_hops=2048)
    srv = AnnServer(idx, params, max_batch=64, buckets=(16, 64))
    t0 = time.perf_counter()
    srv.submit_many(queries)
    out = srv.drain()
    serve_s = time.perf_counter() - t0
    ids = np.stack([r[0] for r in out])
    rec = np.mean([len(set(ids[i].tolist()) & set(gt_i[i].tolist())) / k
                   for i in range(len(out))])
    print(f"served {srv.stats.n_requests} requests in {srv.stats.n_batches} "
          f"batches → recall@{k}={rec:.3f}, "
          f"{srv.stats.n_requests / serve_s:.0f} queries/s "
          f"(host wall clock, compiles included) on {where}")

    # ---- the sharded variant: one shard per visible device ----
    devs = jax.devices()
    S = len(devs)
    print(f"\nsharded serving: {S} shard(s) over {S} {dev.platform} device(s)…")
    mesh = Mesh(np.array(devs), ("data",))
    sidx = place_sharded(build_sharded(base, S, bp, quantized=True), mesh)
    run = make_sharded_search(mesh, shard_axes=("data",), query_axis=None,
                              merge="all_gather", quantized=True)
    ids, _ = run(sidx, jnp.asarray(queries), params)
    ids = np.asarray(ids)
    rec = np.mean([len(set(ids[i].tolist()) & set(gt_i[i].tolist())) / k
                   for i in range(len(queries))])
    print(f"  {S}-shard sharded index recall@{k} = {rec:.3f}")


if __name__ == "__main__":
    main()
