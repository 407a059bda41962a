#!/usr/bin/env python3
"""Records the small TPU trace that ``test_bench_program_trace.py`` reads.

    python3 tests/bench/make_program_trace_fixture.py OUT_DIR   # one TPU chip

Three batches of 8 queries through ``repro.serve.AnnServer`` on a random
graph of 4,096 rows of 128 floats (16 neighbours each), a few lock-step hop
iterations each (``max_hops`` 6), served with a ``Tracer``, whose mirror
puts the ``serve.*`` spans on the host plane.  Each batch runs inside
the harness's ``batch_form`` and ``device_execute`` labels, with a 5 ms
``generator_wait`` after it, all inside a ``window`` annotation.  Writes the
``.xplane.pb`` as ``OUT_DIR/trace.xplane.pb`` and the served program's
compiled HLO as ``OUT_DIR/program.hlo``.
"""

from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

N, D, M, B = 4096, 128, 16, 8


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.core import GraphIndex, SearchParams
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve import AnnServer

    if jax.default_backend() != "tpu":
        print("make_program_trace_fixture: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    graph = GraphIndex(
        vectors=jnp.asarray(rng.integers(0, 256, (N, D)), jnp.float32),
        neighbors=jnp.asarray(rng.integers(0, N, (N, M)), jnp.int32),
        medoid=jnp.int32(0))
    params = SearchParams(k=10, l0=10, l_max=32, max_hops=6)
    queries = rng.integers(0, 256, (4 * B, D)).astype(np.float32)
    srv = AnnServer(graph, params, max_batch=B, buckets=(B,),
                    metrics=MetricsRegistry(), tracer=Tracer())
    srv.submit_many(queries[:B])
    srv.drain()                                     # compiles
    hlo = srv.compile(jnp.asarray(queries[:B])).as_text()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("window"):
        for i in range(1, 4):
            with TraceAnnotation("batch_form"):
                rows = queries[i * B:(i + 1) * B]
            with TraceAnnotation("device_execute"):
                srv.submit_many(rows)
                srv.drain()
            with TraceAnnotation("generator_wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    Path(out).mkdir(parents=True, exist_ok=True)
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, Path(out) / "trace.xplane.pb")
    # source locations relative to the checkout, wherever it lies
    (Path(out) / "program.hlo").write_text(hlo.replace(f"{ROOT}/", ""))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
