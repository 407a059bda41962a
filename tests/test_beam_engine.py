"""Beam-engine self-consistency: determinism goldens, counter invariants,
and unit tests for the packed visited bitset and the tiled gather+L2 kernel.

The engine's *correctness* contract lives in ``tests/test_conformance.py``
(brute-force oracle + the paper's (1/δ) bound — implementation-independent).
This file pins the engine's *behavioral* contract instead:

* **W=1 determinism goldens** — greedy best-first is a deterministic
  schedule: identical ids/dists/hop-counts across runs and across distance
  backends (jnp vs the Pallas kernels, which must be bit-compatible enough
  that tie-breaks never flip on clustered data).
* **Counter invariants** — ``n_encounters`` counts candidate encounters
  pre-dedup, so it dominates ``n_dist_comps`` everywhere, and widening the
  frontier (W↑) or the stop margin (α↑) can only increase the measured
  work (Exp-5's metric must be monotone in the knobs that widen search).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BuildParams,
    SearchParams,
    build_approx,
    build_emqg,
    probing_search,
    search,
)
from repro.core.bitset import (
    bitset_make,
    bitset_set,
    bitset_test,
    bitset_words,
    unique_per_row,
)
from repro.kernels.l2dist import ref as l2ref
from repro.kernels.l2dist.ops import gather_l2_tiled

from conftest import recall_at_k


@pytest.fixture(scope="module")
def graph(small_corpus):
    p = BuildParams(max_degree=24, beam_width=48, t=24, iters=3, block=512)
    return build_approx(small_corpus["base"], p)


@pytest.fixture(scope="module")
def emqg(small_corpus):
    p = BuildParams(max_degree=24, beam_width=48, t=24, iters=2, block=512,
                    align_degree=True)
    return build_emqg(small_corpus["base"], p)


def _params(mode: str, beam_width: int) -> SearchParams:
    if mode == "fixed":
        return SearchParams(k=10, l0=48, l_max=48, adaptive=False,
                            max_hops=512, beam_width=beam_width)
    assert mode == "adaptive"
    return SearchParams(k=10, l0=10, l_max=96, alpha=1.5, adaptive=True,
                        max_hops=2048, beam_width=beam_width)


# ---------------------------------------------------------------------------
# W=1 determinism goldens.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fixed", "adaptive"])
def test_w1_run_to_run_determinism(graph, small_corpus, mode):
    """Greedy best-first (W=1) is a deterministic schedule: two runs must
    agree bit-for-bit on ids and exactly on every counter."""
    q = jnp.asarray(small_corpus["queries"])
    p = _params(mode, beam_width=1)
    r1 = search(graph, q, p)
    r2 = search(graph, q, p)
    assert (np.asarray(r1.ids) == np.asarray(r2.ids)).all()
    np.testing.assert_array_equal(np.asarray(r1.dists), np.asarray(r2.dists))
    for f in ("n_dist_comps", "n_encounters", "n_hops", "final_l"):
        np.testing.assert_array_equal(np.asarray(getattr(r1, f)),
                                      np.asarray(getattr(r2, f)))


@pytest.mark.parametrize("mode", ["fixed", "adaptive"])
def test_w1_backend_self_parity(graph, small_corpus, mode):
    """The jnp and Pallas distance backends drive the identical schedule:
    same ids, same hop counts, distances equal to kernel tolerance."""
    q = jnp.asarray(small_corpus["queries"][:16])
    p = _params(mode, beam_width=1)
    if mode == "adaptive":     # keep interpret-mode Pallas inside CI budget
        p = SearchParams(**{**p.__dict__, "l_max": 32, "max_hops": 256})
    r_jnp = search(graph, q, p, backend="jnp")
    for backend in ("kernel", "kernel_tiled"):
        r_k = search(graph, q, p, backend=backend)
        assert (np.asarray(r_jnp.ids) == np.asarray(r_k.ids)).all(), backend
        np.testing.assert_array_equal(np.asarray(r_jnp.n_hops),
                                      np.asarray(r_k.n_hops))
        np.testing.assert_allclose(np.asarray(r_jnp.dists),
                                   np.asarray(r_k.dists), rtol=1e-4,
                                   atol=1e-4)


def test_probing_run_to_run_determinism(emqg, small_corpus):
    q = jnp.asarray(small_corpus["queries"])
    p = _params("fixed", beam_width=1)
    r1 = probing_search(emqg, q, p)
    r2 = probing_search(emqg, q, p)
    assert (np.asarray(r1.ids) == np.asarray(r2.ids)).all()
    np.testing.assert_array_equal(np.asarray(r1.n_encounters),
                                  np.asarray(r2.n_encounters))


# ---------------------------------------------------------------------------
# Counter invariants (n_encounters monotonicity).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fixed", "adaptive"])
def test_encounters_dominate_dist_evals(graph, small_corpus, mode):
    """Encounters are pre-dedup, distance evals post-dedup: per query,
    ``n_encounters ≥ n_dist_comps`` always (the bitset can only remove)."""
    q = jnp.asarray(small_corpus["queries"])
    r = search(graph, q, _params(mode, beam_width=1))
    assert (np.asarray(r.n_encounters) >= np.asarray(r.n_dist_comps)).all()


def test_encounters_monotone_in_beam_width(graph, small_corpus):
    """Wider frontiers do speculative expansions: mean encounters must be
    weakly increasing in W (per-query counts may reorder, the aggregate
    work metric may not shrink)."""
    q = jnp.asarray(small_corpus["queries"])
    means = []
    for w in (1, 2, 4, 8):
        r = search(graph, q, _params("adaptive", beam_width=w))
        means.append(float(np.mean(np.asarray(r.n_encounters))))
    for lo, hi in zip(means, means[1:]):
        assert hi >= lo * 0.98, means


def test_encounters_monotone_in_alpha(graph, small_corpus):
    """Larger α ⇒ stricter stop rule ⇒ weakly more encounters (Alg. 3)."""
    q = jnp.asarray(small_corpus["queries"])
    means = []
    for alpha in (1.0, 1.2, 1.5):
        p = SearchParams(k=10, l0=10, l_max=96, alpha=alpha, adaptive=True,
                         max_hops=2048, beam_width=1)
        r = search(graph, q, p)
        means.append(float(np.mean(np.asarray(r.n_encounters))))
    assert means[0] <= means[1] <= means[2], means


def test_probing_encounters_dominate(emqg, small_corpus):
    q = jnp.asarray(small_corpus["queries"])
    r = probing_search(emqg, q, _params("fixed", beam_width=1))
    assert (np.asarray(r.n_encounters)
            >= np.asarray(r.n_dist_comps)).all()


# ---------------------------------------------------------------------------
# Engine options.
# ---------------------------------------------------------------------------

def test_beam_width_sweep_recall(graph, small_corpus):
    q = jnp.asarray(small_corpus["queries"])
    for w in (1, 2, 4, 8):
        r = search(graph, q, _params("adaptive", beam_width=w))
        assert recall_at_k(r.ids, small_corpus["gt_i"], 10) > 0.85, w


def test_beam_width_zero_rejected(graph, emqg, small_corpus):
    q = jnp.asarray(small_corpus["queries"][:2])
    p = SearchParams(k=3, l0=8, l_max=16, beam_width=0)
    with pytest.raises(ValueError, match="beam_width"):
        search(graph, q, p)
    with pytest.raises(ValueError, match="beam_width"):
        probing_search(emqg, q, p)


def test_faithful_prune_composes_with_beam_options(graph, small_corpus):
    """faithful_prune runs on the batch engine and composes with any
    beam_width and backend — no delegation, no rejection, no warning."""
    import warnings

    q = jnp.asarray(small_corpus["queries"])
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for w, backend in ((1, "jnp"), (4, "jnp"), (2, "kernel_tiled")):
            p = SearchParams(k=10, l0=10, l_max=48, alpha=1.3, adaptive=True,
                             max_hops=512, beam_width=w)
            qq = q if backend == "jnp" else q[:8]
            r = search(graph, qq, p, faithful_prune=True, backend=backend)
            assert np.isfinite(np.asarray(r.dists)).all(), (w, backend)
    r1 = search(graph, q, SearchParams(k=10, l0=10, l_max=48, alpha=1.3,
                                       adaptive=True, max_hops=512),
                faithful_prune=True)
    assert recall_at_k(r1.ids, small_corpus["gt_i"], 10) > 0.4


def test_faithful_prune_reinsertion_reevaluates(graph, small_corpus):
    """The literal prune clears visited bits of pruned-unexpanded nodes, so
    they can be re-encountered and re-evaluated once ``l`` grows — its
    n_dist may exceed the default engine's (which never re-evaluates)."""
    q = jnp.asarray(small_corpus["queries"])
    p = SearchParams(k=10, l0=10, l_max=96, alpha=1.5, adaptive=True,
                     max_hops=2048, beam_width=1)
    r_def = search(graph, q, p)
    r_fp = search(graph, q, p, faithful_prune=True)
    # both deterministic
    r_fp2 = search(graph, q, p, faithful_prune=True)
    assert (np.asarray(r_fp.ids) == np.asarray(r_fp2.ids)).all()
    # the faithful variant must still produce finite, sorted results
    d = np.asarray(r_fp.dists)
    assert np.isfinite(d).all() and (np.diff(d, axis=1) >= -1e-5).all()
    assert np.asarray(r_def.ids).shape == np.asarray(r_fp.ids).shape


def test_beam_width_clamped_to_buffer(graph, small_corpus):
    """W larger than the candidate buffer must clamp, not crash."""
    q = jnp.asarray(small_corpus["queries"][:2])
    wide = SearchParams(k=3, l0=4, l_max=4, beam_width=64)
    narrow = SearchParams(k=3, l0=4, l_max=4, beam_width=5)  # == l_max+1
    r_wide = search(graph, q, wide)
    r_narrow = search(graph, q, narrow)
    assert (np.asarray(r_wide.ids) == np.asarray(r_narrow.ids)).all()


# ---------------------------------------------------------------------------
# Visited bitset.
# ---------------------------------------------------------------------------

def test_bitset_basic():
    bits = bitset_make(2, 100)
    assert bits.shape == (2, bitset_words(100))
    ids = jnp.asarray([[0, 31, 32, 99], [5, 64, -1, 5]], jnp.int32)
    # duplicate 5 in row 1 → dedup before set (the engine invariant)
    uniq = unique_per_row(ids, ids >= 0)
    bits = bitset_set(bits, uniq)
    probe = jnp.asarray([[0, 31, 32, 99, 1, 33], [5, 64, 0, 6, 99, -1]],
                        jnp.int32)
    got = np.asarray(bitset_test(bits, probe))
    assert got.tolist() == [[True, True, True, True, False, False],
                            [True, True, False, False, False, False]]


def test_bitset_invalid_ids_noop():
    bits = bitset_make(1, 64)
    bits2 = bitset_set(bits, jnp.asarray([[-1, -1]], jnp.int32))
    assert (np.asarray(bits2) == 0).all()
    assert not np.asarray(
        bitset_test(bits2, jnp.asarray([[-1]], jnp.int32)))[0, 0]


def test_bitset_randomized_vs_python_set():
    rng = np.random.default_rng(0)
    n, rounds = 257, 6
    bits = bitset_make(1, n)
    seen = set()
    for _ in range(rounds):
        batch = rng.integers(0, n, size=(1, 16)).astype(np.int32)
        fresh_np = np.asarray(
            [[int(v) not in seen for v in batch[0]]])
        got = ~np.asarray(bitset_test(bits, jnp.asarray(batch)))
        assert (got == fresh_np).all()
        uniq = unique_per_row(jnp.asarray(batch), jnp.asarray(fresh_np))
        bits = bitset_set(bits, uniq)
        seen.update(int(v) for v in batch[0])


def test_unique_per_row():
    ids = jnp.asarray([[7, 3, 7, 3, 9, -1], [1, 1, 1, 1, 1, 1]], jnp.int32)
    fresh = ids >= 0
    out = np.asarray(unique_per_row(ids, fresh))
    assert sorted(v for v in out[0] if v >= 0) == [3, 7, 9]
    assert sorted(v for v in out[1] if v >= 0) == [1]
    # valid prefix is sorted ascending, invalid tail is -1
    row = out[0]
    valid = row[row >= 0]
    assert (np.diff(valid) > 0).all()


# ---------------------------------------------------------------------------
# Tiled gather kernel.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,M,d", [
    (2, 16, 24), (4, 30, 128), (1, 7, 65), (3, 24, 33),
    # the shape-derived query block: B never a multiple of it, K from the
    # start call's 1 to W=4's 256, d below, off and at the lane width
    (1, 1, 24), (3, 64, 65), (9, 256, 128), (257, 64, 128), (257, 1, 24),
    (9, 64, 24), (1, 64, 65), (3, 1, 128)])
def test_gather_l2_tiled_vs_ref(B, M, d):
    rng = np.random.default_rng(B * 100 + M + d)
    n = 200
    base = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ids = rng.integers(0, n, (B, M)).astype(np.int32)
    ids[0, 0] = -1                      # INVALID handling: first slot,
    ids[-1, -1] = -1                    # last slot,
    if B > 2:
        ids[B // 2] = -1                # and a wholly INVALID row
    ids = jnp.asarray(ids)
    qs = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))
    out = np.asarray(gather_l2_tiled(base, ids, qs))
    expect = np.asarray(l2ref.gather_l2_ref(base, jnp.maximum(ids, 0), qs))
    assert out.shape == (B, M)
    mask = np.asarray(ids) >= 0
    assert np.isinf(out[~mask]).all()
    np.testing.assert_allclose(out[mask], expect[mask], rtol=1e-4, atol=1e-3)


def test_gather_l2_tiled_matches_single_row():
    from repro.kernels.l2dist.ops import gather_l2

    rng = np.random.default_rng(11)
    base = jnp.asarray(rng.normal(size=(64, 48)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (4, 24)).astype(np.int32))
    qs = jnp.asarray(rng.normal(size=(4, 48)).astype(np.float32))
    a = np.asarray(gather_l2(base, ids, qs))
    b = np.asarray(gather_l2_tiled(base, ids, qs))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Serving layer.
# ---------------------------------------------------------------------------

def test_server_backends_agree(graph, small_corpus):
    """W=1 determinism holds through the serving layer: the same queries
    served under different distance backends return identical ids."""
    from repro.serve.ann_server import AnnServer

    params = SearchParams(k=10, l0=10, l_max=32, alpha=1.5, adaptive=True,
                          max_hops=256, beam_width=1)
    out = {}
    for backend in ("jnp", "kernel_tiled"):
        srv = AnnServer(graph, params, max_batch=8, buckets=(8,),
                        backend=backend)
        srv.submit_many(small_corpus["queries"][:8])
        out[backend] = srv.drain()
    for (ids_a, d_a), (ids_b, d_b) in zip(out["jnp"], out["kernel_tiled"]):
        assert (ids_a == ids_b).all()
        np.testing.assert_allclose(d_a, d_b, rtol=1e-4, atol=1e-4)


def test_server_rejects_unknown_engine(graph):
    from repro.serve.ann_server import AnnServer

    params = SearchParams(k=5, l0=8, l_max=16)
    with pytest.raises(ValueError, match="unknown engine"):
        AnnServer(graph, params, engine="legacy")


# ---------------------------------------------------------------------------
# The lock-step counter and the hop-phase scopes.
# ---------------------------------------------------------------------------

HOP_SCOPES = ("hop.select", "hop.expand", "hop.visited", "hop.distance",
              "hop.merge", "hop.transition")


def _path_graph(n: int = 64):
    """Nodes 0..n-1 on a line, each linked to its two neighbours."""
    from repro.core import GraphIndex

    nbrs = np.full((n, 2), -1, np.int32)
    nbrs[1:, 0] = np.arange(n - 1)
    nbrs[:-1, 1] = np.arange(1, n)
    return GraphIndex(vectors=jnp.arange(n, dtype=jnp.float32)[:, None],
                      neighbors=jnp.asarray(nbrs), medoid=jnp.int32(0))


def test_n_iters_max_is_the_loop_trip_count(monkeypatch):
    """Every query starts at node 0 of a path: the one at 60 walks about 60
    hops, the others stop after a few.  The largest ``n_iters`` equals the
    trips of a ``while_loop`` that counts its own."""
    import jax

    from repro.core.search import _beam_search_batch, make_batch_dist_fn

    graph = _path_graph()
    queries = jnp.asarray([[1.0], [2.0], [3.0], [60.0]])
    p = SearchParams(k=1, l0=2, l_max=4, max_hops=512)
    trips = []
    real = jax.lax.while_loop

    def counting(cond, body, init):
        out, n = real(lambda c: cond(c[0]),
                      lambda c: (body(c[0]), c[1] + 1), (init, jnp.int32(0)))
        trips.append(int(n))
        return out

    monkeypatch.setattr(jax.lax, "while_loop", counting)
    st = _beam_search_batch(graph, queries, jnp.zeros((4,), jnp.int32), p,
                            make_batch_dist_fn(graph.vectors, "jnp"))
    it = np.asarray(st.n_iters)
    assert trips and it.max() == trips[0] > 50
    assert it.min() < 10
    # each row counts the iterations it was active in: one per expansion
    # at W=1, plus the iteration in which its window ran dry
    np.testing.assert_array_equal(it, np.asarray(st.n_hops) + 1)
    monkeypatch.undo()
    res = search(graph, queries, p, start=jnp.zeros((4,), jnp.int32),
                 backend="jnp")
    np.testing.assert_array_equal(np.asarray(res.n_iters), it)


def test_compiled_search_has_every_hop_scope():
    """The loop body's phases reach the compiled program as the op_name
    metadata of its instructions, one scope each."""
    import re

    graph = _path_graph()
    queries = jnp.zeros((8, 1), jnp.float32)
    hlo = search.lower(graph, queries, SearchParams(k=1, l0=2, l_max=4),
                       backend="jnp").compile().as_text()
    found = {s for name in re.findall(r'op_name="([^"]*)"', hlo)
             for s in name.split("/") if s.startswith("hop.")}
    assert found == set(HOP_SCOPES)


def test_compiled_probing_search_has_every_hop_scope(emqg):
    """The probing engine's loop carries the same six scopes, and its
    RaBitQ estimates their own, ``hop.estimate``."""
    import re

    queries = jnp.zeros((8, emqg.dim), jnp.float32)
    hlo = probing_search.lower(emqg, queries, SearchParams(k=1, l0=2, l_max=4),
                               backend="jnp").compile().as_text()
    found = {s for name in re.findall(r'op_name="([^"]*)"', hlo)
             for s in name.split("/") if s.startswith("hop.")}
    assert found == set(HOP_SCOPES) | {"hop.estimate"}
