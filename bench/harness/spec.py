"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; each
lives in a file of its own:

* configuration: the ``file`` its ``configs`` entry gives;
* mix: ``bench/traffic/<traffic>.json``;
* system under test: ``bench/systems/<system>.py``, named by the
  configuration;
* per-layer metric: ``bench/metrics/<name>.py``, a ``read(run)`` that
  returns a number, or None where it finds nothing to read.

So a cell, a configuration, a mix or a metric is added with new files and
new entries, and no file that is there changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _with_rehearsal(d: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in d.items() if k != "rehearse"}
    if rehearse:
        out.update(d.get("rehearse", {}))
    return out


class Cell:
    """One ``workloads`` entry with its configuration, mix and metrics."""

    def __init__(self, workload: str, rehearse: bool = False):
        self.root = root = ROOT
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = self.entry["chips"]
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _with_rehearsal(
            json.loads((root / conf["file"]).read_text()), rehearse)
        self.traffic = _with_rehearsal(json.loads(
            (root / "bench" / "traffic" / f"{self.entry['traffic']}.json")
            .read_text()), rehearse)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])
                          and any(e["name"] == m["moves"]
                                  for e in self.end_to_end)]

    def system(self):
        return load_module(
            self.root / "bench" / "systems" / f"{self.config['system']}.py",
            f"bench_system_{self.config['system']}")

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))
