"""The comparison that decides ``correct`` fails what it must fail.

* The control — the float64 reference computed in bfloat16, put in the
  program's place — comes out as not correct, by ``dist_err``.
* A whole run (``--rehearse`` sizes, on the CPU, in this process) with the
  timed path broken underneath comes out as not correct, once for each
  fault a cell can have: a search that returns its state unchanged, half
  of each batch's answers left out, and an answer altered where the
  search produces it.  (One chip: there is no exchange between chips to
  leave out.)
"""

import dataclasses
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import cell as runner  # noqa: E402
from harness.spec import Cell  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = Cell(name, rehearse=True)
    v = runner.control(cell, 2**31 + 5, 2.0)
    assert v["correct"] is False
    c = v["checks"]["dist_err"]
    assert c["value"] > 10 * c["limit"]
    # the control's answers are near-exact: only the precision fails it
    assert v["recall"] > 0.9
    assert v["checks"]["bad_answers"]["value"] == 0


def _state_unchanged(monkeypatch):
    from repro.serve import AnnServer

    orig = AnnServer._search

    def search(self, queries, params=None, *a, **kw):
        p = dataclasses.replace(params or self.params, max_hops=0)
        return orig(self, queries, p, *a, **kw)

    monkeypatch.setattr(AnnServer, "_search", search)


def _half_left_out(monkeypatch):
    from repro.serve import AnnServer

    orig = AnnServer.drain

    def drain(self):
        out = orig(self)
        return out[: len(out) // 2]

    monkeypatch.setattr(AnnServer, "drain", drain)


def _answer_altered(monkeypatch):
    from repro.serve import AnnServer

    orig = AnnServer._search

    def search(self, queries, *a, **kw):
        res = orig(self, queries, *a, **kw)
        n = self.index.n
        return dataclasses.replace(
            res, ids=res.ids.at[:, 0].set((res.ids[:, 0] + n // 2) % n))

    monkeypatch.setattr(AnnServer, "_search", search)


FAULTS = {"state_unchanged": (_state_unchanged, "bad_answers"),
          "half_left_out": (_half_left_out, "missing"),
          "answer_altered": (_answer_altered, "dist_err")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    cell = Cell(name, rehearse=True)
    result = runner.run(cell, 2**31 + 9, 1.0, False, time.perf_counter())
    assert result["correct"] is False
    c = result["checks"][caught_by]
    assert c["value"] > c["limit"]
