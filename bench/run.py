#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1> [--rehearse]

The cell (a ``workloads`` entry of ``BENCHMARK.json``) names a
configuration and a traffic mix, each a file of its own; see
``bench/harness/spec.py``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` on a TPU ``breakdown``, and
last ``checks``, each number compared with its limit.

Without ``--rehearse`` a machine whose JAX backend is not a TPU, or that
has fewer chips than the cell asks for, exits non-zero and prints no
result.  ``--rehearse`` runs the cell's tiny sizes on the CPU (a
control-flow check for the tests, never a measurement).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, for the tests")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from harness.spec import Cell

    cell = Cell(args.workload, rehearse=args.rehearse)

    import jax

    if not args.rehearse and jax.default_backend() != "tpu":
        print(f"bench: JAX backend is {jax.default_backend()!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    # every program, however quick to compile, is cached: a warm set-up
    # then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import cell as runner

    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START)
    runner.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
