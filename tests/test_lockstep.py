"""The compacting lock-step driver (``repro.core.lockstep``): a row's result
does not depend on its batch-mates, so a batch that compacts as its rows
finish answers exactly as the same queries run in batches too small to
compact, and runs fewer row slots."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BuildParams,
    SearchParams,
    ags_search,
    build_emqg,
    probing_search,
    search,
)
from repro.core import lockstep


@pytest.mark.parametrize("step,min_rung,batch,want", [
    (2, 16, 1024, (1024, 512, 256, 128, 64, 32, 16)),
    (4, 16, 1024, (1024, 256, 64, 16)),
    (2, 16, 100, (100, 32, 16)),
    (2, 16, 32, (32, 16)),
    (2, 16, 31, (31,)),
    (2, 16, 16, (16,)),
    (2, 8, 1, (1,)),
])
def test_ladder(monkeypatch, step, min_rung, batch, want):
    """Each rung at most the one above it over the step, the smallest the
    minimum rung; a batch under step × minimum runs in one stage."""
    monkeypatch.setattr(lockstep, "LADDER_STEP", step)
    monkeypatch.setattr(lockstep, "MIN_RUNG", min_rung)
    assert lockstep.ladder(batch) == want


B = 64
PARAMS = SearchParams(k=10, l0=10, l_max=128, alpha=1.2, adaptive=True,
                      max_hops=4096)


@pytest.fixture(scope="module")
def index(small_corpus):
    p = BuildParams(max_degree=24, beam_width=48, t=24, iters=2, block=512,
                    align_degree=True)
    return build_emqg(small_corpus["base"], p)


def _run(engine, index, queries, start):
    if engine == "search":
        return search(index.graph, queries, PARAMS, start=start,
                      backend="jnp")
    if engine == "search_faithful":
        return search(index.graph, queries, PARAMS, start=start,
                      faithful_prune=True, backend="jnp")
    if engine == "search_candidates":
        return search(index.graph, queries, PARAMS, start=start,
                      with_candidates=True, backend="jnp")
    if engine == "probing_search":
        return probing_search(index, queries, PARAMS, start=start,
                              backend="jnp")
    return ags_search(index, queries, PARAMS, start=start, backend="jnp")


def _fields(out):
    """{name: host array} of a result and, with candidates, its buffers."""
    out = jax.device_get(out)
    res, bufs = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
    got = {f.name: getattr(res, f.name) for f in dataclasses.fields(res)
           if getattr(res, f.name) is not None}
    got.update({f"candidates{i}": b for i, b in enumerate(bufs)})
    return got


@pytest.mark.parametrize("engine", ["search", "search_faithful",
                                    "search_candidates", "probing_search",
                                    "ags_search"])
def test_compacted_batch_answers_as_uncompacted_batches(index, small_corpus,
                                                        engine):
    """64 queries from random starting nodes finish over a wide spread of
    iterations, so the loop passes through several stages of the ladder
    (64, 32, 16).  Every result field and candidate buffer equals that of
    the same queries run in batches of the smallest rung, which do not
    compact; only the resident slots fall below B × the trip count."""
    assert lockstep.ladder(B) == (64, 32, 16)
    rung = lockstep.MIN_RUNG
    queries = jnp.asarray(small_corpus["queries"][:B])
    start = jnp.asarray(np.random.default_rng(7).integers(
        0, index.graph.n, B), jnp.int32)

    whole = _fields(_run(engine, index, queries, start))
    parts = [_fields(_run(engine, index, queries[i:i + rung],
                          start[i:i + rung])) for i in range(0, B, rung)]
    assert set(whole) == set(parts[0])
    for name in whole:
        if name == "n_resident":
            continue
        np.testing.assert_array_equal(
            whole[name], np.concatenate([p[name] for p in parts]),
            err_msg=name)

    trips = int(whole["n_iters"].max())
    resident = whole["n_resident"]
    assert resident.max() == trips
    assert resident.sum() < B * trips
    # rows left out at each compaction keep the trip count they had there
    assert len(np.unique(resident)) >= 3
    for p in parts:
        assert p["n_resident"].sum() == rung * int(p["n_iters"].max())
