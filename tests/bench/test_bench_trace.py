"""The reduction from a profiler trace to device metrics, on a small trace
recorded on a TPU v5e (``make_trace_fixture.py``: three ``gather_l2_tiled``
steps inside a ``window`` annotation), the peaks table, and the kernel
work computed from the compiled program's shapes."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import kernels, peaks, trace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data"


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_merge_and_gaps():
    busy = trace.merge([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25)
    assert busy == [(1, 3), (5, 12), (20, 25)]
    assert list(trace.gaps(busy, 0, 30)) == [(0, 1), (3, 5), (12, 20),
                                             (25, 30)]
    labels = trace.Labels({"batch_form": [(0, 4)],
                           "device_execute": [(4, 12)],
                           "fan_out": [(15, 16)]})
    assert labels.split(2, 18) == [("batch_form", 2), ("device_execute", 8),
                                   ("other", 3), ("fan_out", 1),
                                   ("other", 2)]
    assert labels.split(5, 6) == [("device_execute", 1)]
    assert labels.split(20, 22) == [("other", 2)]


def test_gather_l2_tiled_work():
    ops = [("s32", (1024, 8, 1, 8)), ("f32", (32768, 128)),
           ("f32", (1024, 1, 128))]
    w = kernels.gather_l2_tiled_work(ops)
    rows = 1024 * 64
    assert w["bytes"] == rows * 128 * 4 + 1024 * 128 * 4 + rows * 8
    assert w["flops"] == 3 * rows * 128


def test_self_times_nested():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 50), ("c", 120, 130)]
    st = trace.self_times(ops)
    assert st == {"while": 70e-9, "a": 20e-9, "b": 10e-9, "c": 10e-9}


def test_reduce_recorded_trace():
    """The recorded trace: three steps of one ``gather_l2_tiled`` call on
    [64, 16] ids (R=8: ids blocked [64, 2, 1, 8]) over 4,096 rows of 128
    floats, with a 5 ms ``generator_wait`` after each."""
    r = trace.reduce(trace.load(str(FIXTURE)))
    assert r["devices"] == 1
    assert abs(r["window_s"] - 0.019371729) < 1e-9
    assert 0 < r["busy_s"] < 0.01 * r["window_s"]
    k = r["kernels"]["gather_l2_tiled"]
    assert k["calls"] >= 2
    per_call = 64 * 16 * 128 * 4 + 64 * 128 * 4 + 64 * 16 * 8
    assert k["bytes"] == k["calls"] * per_call
    assert 0 < k["seconds"] <= r["busy_s"]
    top = r["device_ops"][0][0]
    assert top.startswith("gather_l2_tiled_pallas") and "{" not in top
    idle = dict(r["idle_gaps"])
    assert set(idle) <= {"generator_wait", "device_execute", "batch_form",
                         "fan_out", "other"}
    assert abs(sum(idle.values()) + r["busy_s"] - r["window_s"]) < 1e-6
    assert idle["generator_wait"] > 0.01
