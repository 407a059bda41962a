"""The benchmark's corpus generator: SIFT's shape, from the seed."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness.data import sift_like  # noqa: E402


CONFIGS = {c["name"]: c["file"] for c in
           json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]}


def _config(name):
    return json.loads((ROOT / CONFIGS[name]).read_text())


def _small(cfg, queries=64):
    d = dict(cfg["data"], queries=queries)
    return sift_like(4096, dim=cfg["dim"], **d)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_values_are_sift_shaped(name):
    cfg = _config(name)
    x, q = _small(cfg)
    assert cfg["dim"] == 128
    assert x.shape == (4096, 128) and q.shape == (64, 128)
    assert x.dtype == np.float32 and q.dtype == np.float32
    for a in (x, q):
        assert (a == np.rint(a)).all()
        assert a.min() >= 0 and a.max() <= 255
    # a share of zeros, as SIFT's histograms have; no two vectors alike
    assert 0.1 < (x == 0).mean() < 0.6
    assert np.unique(x, axis=0).shape[0] == x.shape[0]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_assumed_sizes_are_recorded(name):
    cfg = _config(name)
    for key in ("latent_dim", "clusters", "corpus_seed", "query_seed",
                "queries"):
        assert key in cfg["data"] and key in cfg["assumed"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_query_set_is_the_configurations(name):
    """The corpus and the query set come from the configuration alone: no
    run seed enters them, so every run sends the same queries."""
    cfg = _config(name)
    a, b = _small(cfg), _small(cfg)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert cfg["data"]["corpus_seed"] != cfg["data"]["query_seed"]


def test_seeds_decide_corpus_and_queries():
    """The corpus seed decides the corpus and the query seed the queries:
    the same seeds give the same arrays, another seed other ones."""
    a = sift_like(2048, 16, 11, 3)
    b = sift_like(2048, 16, 11, 3)
    c = sift_like(2048, 16, 12, 3)
    d = sift_like(2048, 16, 11, 4)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(a[0], d[0]) and not np.array_equal(a[1], d[1])


def test_large_seed():
    x, q = sift_like(256, 8, 0, 2**31 + 12345)
    assert x.shape == (256, 128) and np.isfinite(x).all()


def test_queries_are_held_out():
    """Queries are fresh draws: none equals a corpus row, and their
    nearest corpus rows lie at about the corpus's own nearest-neighbour
    distance (same distribution)."""
    x, q = sift_like(4096, 64, 0, 5)
    d_q = np.sqrt(((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)).min(1)
    xs = x[:64]
    d_x = np.sqrt(((xs[:, None, :] - x[None, :, :]) ** 2).sum(-1))
    d_x[np.arange(64), np.arange(64)] = np.inf
    assert (d_q > 0).all()
    assert 0.5 < np.median(d_q) / np.median(d_x.min(1)) < 2.0
