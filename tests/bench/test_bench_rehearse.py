"""Every cell of ``BENCHMARK.json`` run end to end at its ``--rehearse``
size on the CPU, as the driver runs it (a control-flow check: no number
here is a measurement), and the refusals the contract asks for."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_bench(*args, cwd=ROOT, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *BENCH["command"][1:], *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def listed(cell, kind, trace):
    out = []
    for m in BENCH[kind]:
        if cell not in m.get("workloads", [cell]):
            continue
        # device metrics come only from a chip's trace, never the CPU's
        if trace and m["source"] == "device_trace":
            continue
        out.append(m["name"])
    return out


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    p = run_bench("--workload", cell, "--seed", str(2**31 + 77),
                  "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(last)[:5] == KEYS
    assert list(last)[-1] == "checks"
    assert set(last) == set(KEYS) | {"checks"}
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" in last["device"]
    kind = "per_layer" if trace else "end_to_end"
    want = listed(cell, kind, trace)
    assert sorted(last["metrics"]) == sorted(want)
    units = {m["name"]: m["unit"] for m in BENCH[kind]}
    for name, m in last["metrics"].items():
        assert m["unit"] == units[name] and m["value"] == m["value"]
    # the numbers compared are the last lines of standard error
    tail = p.stderr.strip().splitlines()[-len(last["checks"]):]
    assert [line.split()[1] for line in tail] == list(last["checks"])


def test_refuses_a_cpu_without_rehearse():
    p = run_bench("--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                  "--trace", "0", timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only ``BENCHMARK.json`` and the benchmark's own
    paths has no system to measure: no result, a non-zero exit."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench("--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                  "--trace", "0", "--rehearse", cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
