#!/usr/bin/env python3
"""Records the small TPU trace that ``test_bench_trace.py`` reads.

    python3 tests/bench/make_trace_fixture.py OUT_DIR     # on one TPU chip

Three ``device_execute`` steps inside a ``window`` annotation, each one
``gather_l2_tiled`` call over [64, 16] ids into a corpus of 4,096 rows of
128 floats, with a ``generator_wait`` sleep between them.  Writes the
``.xplane.pb`` as ``OUT_DIR/trace.xplane.pb`` and the compiled program's
HLO as ``OUT_DIR/program.hlo``.
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


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.kernels.l2dist import ops

    if jax.default_backend() != "tpu":
        print("make_trace_fixture: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.integers(0, 256, (4096, 128)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 4096, (64, 16)), jnp.int32)
    q = jnp.asarray(rng.integers(0, 256, (64, 128)), jnp.float32)
    step = jax.jit(lambda b, i, x: ops.gather_l2_tiled(b, i, x) + 1.0)
    step(base, ids, q).block_until_ready()
    hlo = step.lower(base, ids, q).compile().as_text()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("device_execute"):
                step(base, ids, q).block_until_ready()
            with TraceAnnotation("generator_wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    Path(out).mkdir(parents=True, exist_ok=True)
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, Path(out) / "trace.xplane.pb")
    (Path(out) / "program.hlo").write_text(hlo)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
