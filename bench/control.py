#!/usr/bin/env python3
"""The control of a cell's comparison: the float64 reference computed in
bfloat16 instead, put in the program's place, on several seeds.  Its
answers must come out as not correct (PERF.md gives its readings).

    python3 bench/control.py --workload sift-emg.bulk --seconds 51 \\
        --seeds 1,2,3 [--rehearse]

Prints, for each seed, the verdict and each number compared beside its
limit, one JSON object a line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from harness import cell as runner
    from harness.spec import Cell

    if not args.rehearse and jax.default_backend() != "tpu":
        print("control: JAX backend is not a TPU", file=sys.stderr)
        return 2
    cell = Cell(args.workload, rehearse=args.rehearse)
    for seed in (int(s) for s in args.seeds.split(",")):
        v = runner.control(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": v["correct"], "checks": v["checks"],
                          "recall": v["recall"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
