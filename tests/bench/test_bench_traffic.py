"""The traffic generator sends the same work whatever the seed: the seed
orders the fixed query set and draws the arrivals, and nothing else."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import traffic  # noqa: E402

MIX = {"loop": "open", "rate_qps": 50, "max_batch": 8}


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_open_schedule_cycles_the_query_set(seed):
    """Every query is sent once before any is sent twice, and the arrivals
    are exactly ``rate · seconds``, in order, inside the window."""
    s = traffic.open_schedule(MIX, 4.0, 64, np.random.default_rng(seed))
    assert s.due.size == s.qidx.size == 200
    assert (np.diff(s.due) >= 0).all() and 0 <= s.due[0] and s.due[-1] < 4
    counts = np.bincount(s.qidx, minlength=64)
    assert counts.min() == 3 and counts.max() == 4
    assert np.unique(s.qidx[:64]).size == 64


def test_open_schedule_seeds_differ_only_in_order():
    a = traffic.open_schedule(MIX, 2.56, 64, np.random.default_rng(1))
    b = traffic.open_schedule(MIX, 2.56, 64, np.random.default_rng(2))
    c = traffic.open_schedule(MIX, 2.56, 64, np.random.default_rng(1))
    assert np.array_equal(a.qidx, c.qidx) and np.array_equal(a.due, c.due)
    assert not np.array_equal(a.qidx, b.qidx)
    assert np.array_equal(np.sort(a.qidx), np.sort(b.qidx))


def test_closed_loop_sends_fixed_batches():
    """Batch ``i`` holds the same rows for every seed, wrapping round the
    query set; only the order within a batch changes."""
    queries = np.arange(40, dtype=np.float32)[:, None]
    clock = iter(np.arange(0.0, 100.0, 1.0))

    def sent(seed):
        batches = []

        def serve(rows):
            batches.append(rows[:, 0].astype(int))
            return [(np.zeros(1), np.zeros(1)) for _ in rows]

        traffic.closed_loop(serve, queries, {"batch": 16}, 3.5,
                            np.random.default_rng(seed),
                            clock=lambda: next(clock))
        return batches

    a, b = sent(1), sent(2)
    assert len(a) == len(b) == 4
    want = [np.arange(i * 16, (i + 1) * 16) % 40 for i in range(4)]
    for x, y, w in zip(a, b, want):
        assert np.array_equal(np.sort(x), np.sort(w))
        assert np.array_equal(np.sort(y), np.sort(w))
    assert any(not np.array_equal(x, y) for x, y in zip(a, b))
