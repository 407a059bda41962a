"""A configuration, a traffic mix, a per-layer metric and a cell are added
with new files and new ``BENCHMARK.json`` entries alone, on a temporary
copy: no file of the benchmark that was there changes, and the new cell
runs with its new metric."""

import hashlib
import json
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_add_a_cell_with_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in ("bench", "src"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    cfg = json.loads((tmp_path / "bench/configs/sift-emg.json").read_text())
    cfg.update(name="sift-emg-wide", beam_width=4)
    (tmp_path / "bench/configs/sift-emg-wide.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/online-slow.json").write_text(json.dumps({
        "loop": "open", "rate_qps": 30, "max_batch": 16, "buckets": [16]}))
    (tmp_path / "bench/metrics/batches_per_s.slow.py").write_text(
        "def read(run):\n"
        "    h = run.registry.histogram('serve_batch_size')\n"
        "    return h.count / run.served.window_s if h.count else None\n")

    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    new = "sift-emg-wide.slow"
    bench["configs"].append({
        "name": "sift-emg-wide", "source": "https://example.org/cfg",
        "file": "bench/configs/sift-emg-wide.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({"name": new, "config": "sift-emg-wide",
                               "traffic": "online-slow", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "p99_ms":
            m["workloads"].append(new)
    bench["per_layer"].append({
        "name": "batches_per_s.slow", "unit": "batches/s",
        "better": "higher", "source": "program_counter",
        "layer": "serve loop", "moves": "p99_ms", "workloads": [new]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before

    for trace, metric in ((0, "p99_ms"), (1, "batches_per_s.slow")):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", new, "--seed",
             "5", "--seconds", "2", "--trace", str(trace), "--rehearse"],
            cwd=tmp_path, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        last = json.loads(p.stdout.strip().splitlines()[-1])
        assert last["correct"] is True, last["checks"]
        assert metric in last["metrics"]
