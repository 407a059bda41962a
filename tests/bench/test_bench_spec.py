"""``BENCHMARK.json`` keeps to its format: names and units of the allowed
characters, every per-layer metric moving an end-to-end metric its cells
report, every file the harness looks up present."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./\-]+", p)
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_text():
    metrics = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200, (e["name"], key)
                    assert "\n" not in e[key] and "\t" not in e[key]
            if "unit" in e:
                metrics.append(e["name"])
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(metrics) == len(set(metrics))
    assert len(CELLS) == len(BENCH["workloads"])


def test_configs_and_cells():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in CELLS.values())
        assert (ROOT / "bench" / "systems" / f"{cfg['system']}.py").is_file()
    pairs = {(w["config"], w["traffic"]) for w in CELLS.values()}
    assert len(pairs) == len(CELLS)
    for w in CELLS.values():
        assert w["chips"] == 1
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric(metric):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in _cells_of(metric):
        assert cell in CELLS
        assert cell in _cells_of(e2e[metric["moves"]])
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in CELLS:
        reported = [m for m in e2e.values() if cell in _cells_of(m)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(cell in _cells_of(m) for m in BENCH["per_layer"])
