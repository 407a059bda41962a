import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything below is ordinary.

"""Multi-pod dry-run driver.

For every (architecture × input shape) cell, on the single-pod 16×16 mesh
and the 2×16×16 multi-pod mesh:

    lowered  = jit(step, in_shardings=...).lower(*abstract_args)
    compiled = lowered.compile()
    memory_analysis()   → per-device bytes (proves the cell fits HBM)
    cost_analysis()     → HLO FLOPs / bytes for §Roofline
    parse compiled HLO  → per-collective operand bytes for §Roofline

Results are appended to a JSON file (default
``benchmarks/results/dryrun.json``).

Usage:
    python -m repro.launch.dryrun                       # everything
    python -m repro.launch.dryrun --arch internlm2-20b  # one arch
    python -m repro.launch.dryrun --arch sift1m --mesh single
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax

# TPU v5e hardware model (assignment constants)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dims_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(dims_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def parse_collectives(hlo_text: str) -> dict:
    """Per-device wire bytes for every collective in the partitioned module.

    Optimized HLO prints operands as bare %refs, so sizes come from the
    *result* shape (per-device/partitioned), converted to ring-model wire
    traffic with the replica-group size g:
        all-gather        out·(g−1)/g      (result = gathered; recv share)
        all-reduce        2·out·(g−1)/g    (reduce-scatter + all-gather)
        reduce-scatter    out·(g−1)        (input = out·g, ring pass)
        all-to-all        out·(g−1)/g
        collective-permute out              (one send per device)
    '-start' async halves are counted once ('-done' carries no new data).
    """
    out = {c: {"count": 0, "operand_bytes": 0} for c in _COLLECTIVES}
    pat = re.compile(r"=\s+(.+?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        op = m.group(2)
        result_bytes = _shape_bytes(m.group(1))
        if m.group(3):  # '-start' result is a tuple (operand, result, ...)
            result_bytes = result_bytes / 2
        g = _group_size(line)
        if op == "all-gather":
            wire = result_bytes * (g - 1) / g
        elif op == "all-reduce":
            wire = 2 * result_bytes * (g - 1) / g
        elif op == "reduce-scatter":
            wire = result_bytes * (g - 1)
        elif op == "all-to-all":
            wire = result_bytes * (g - 1) / g
        else:  # collective-permute
            wire = result_bytes
        out[op]["count"] += 1
        out[op]["operand_bytes"] += int(wire)
    out["total_operand_bytes"] = sum(v["operand_bytes"]
                                     for k, v in out.items() if isinstance(v, dict))
    return out


def run_cell(arch, shape, mesh, mesh_name: str, verbose: bool = True) -> dict:
    from repro.launch.steps import build_cell

    rec = {"arch": arch.id, "shape": shape.name, "mesh": mesh_name,
           "chips": mesh.devices.size}
    if shape.skip:
        rec["status"] = "skip"
        rec["skip_reason"] = shape.skip
        if verbose:
            print(f"  [{mesh_name}] {arch.id} × {shape.name}: SKIP ({shape.skip})")
        return rec
    t0 = time.time()
    try:
        from repro.launch.hlo_analysis import analyze

        cell = build_cell(arch, shape, mesh)
        lowered = cell.lower()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)       # static (per-HLO-op) view
        loop = analyze(hlo)                 # loop-aware (×trip-count) view
        chips = mesh.devices.size
        raw_flops = float(cost.get("flops", 0.0))
        raw_bytes = float(cost.get("bytes accessed", 0.0))
        # roofline terms from the loop-aware analysis (cost_analysis counts
        # while bodies ONCE — ~50× under for scan-over-layers models; see
        # launch/hlo_analysis.py)
        t_comp = loop["flops"] / PEAK_FLOPS
        t_mem = loop["hbm_bytes"] / HBM_BW
        t_coll = loop["collective_bytes"] / LINK_BW
        per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        rec.update({
            "status": "ok",
            "description": cell.description,
            "compile_s": round(time.time() - t0, 1),
            "model_flops": cell.model_flops,
            "raw_cost_analysis": {"flops": raw_flops,
                                  "bytes_accessed": raw_bytes},
            "hlo_flops_per_device": loop["flops"],
            "hlo_bytes_per_device": loop["hbm_bytes"],
            "collectives_static": coll,
            "collectives": loop["collectives"]
            | {"total_operand_bytes": loop["collective_bytes"]},
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_per_device_bytes": per_dev_bytes,
            },
            "roofline": {
                "compute_s": t_comp,
                "memory_s": t_mem,
                "collective_s": t_coll,
                "bottleneck": max(
                    (("compute", t_comp), ("memory", t_mem),
                     ("collective", t_coll)), key=lambda kv: kv[1])[0],
                "useful_flops_ratio": (cell.model_flops / (loop["flops"] * chips)
                                       if loop["flops"] else 0.0),
            },
        })
        if verbose:
            r = rec["roofline"]
            print(f"  [{mesh_name}] {arch.id} × {shape.name}: OK "
                  f"({rec['compile_s']}s) mem/dev="
                  f"{per_dev_bytes/2**30:.2f}GiB "
                  f"comp={r['compute_s']*1e3:.2f}ms "
                  f"mem={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms "
                  f"→ {r['bottleneck']}")
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"  [{mesh_name}] {arch.id} × {shape.name}: "
                  f"ERROR {rec['error'][:300]}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id filter")
    ap.add_argument("--shape", default=None, help="shape name filter")
    ap.add_argument("--mesh", default="both", choices=("single", "multi", "both"))
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args(argv)

    from repro.configs import all_archs, get_arch
    from repro.launch.mesh import make_production_mesh

    assert len(jax.devices()) == 512, (
        f"dry-run needs 512 placeholder devices, got {len(jax.devices())}")

    archs = [get_arch(args.arch)] if args.arch else all_archs()
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    records = []
    for arch in archs:
        for shape_name, shape in arch.shapes.items():
            if args.shape and shape_name != args.shape:
                continue
            for mesh_name, mesh in meshes:
                records.append(run_cell(arch, shape, mesh, mesh_name))
                jax.clear_caches()

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    existing = []
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
        keys = {(r["arch"], r["shape"], r["mesh"]) for r in records}
        existing = [r for r in existing
                    if (r["arch"], r["shape"], r["mesh"]) not in keys]
    with open(args.out, "w") as f:
        json.dump(existing + records, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skip" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skip, {n_err} error "
          f"→ {args.out}")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
