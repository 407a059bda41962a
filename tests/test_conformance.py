"""Oracle-based conformance suite (marker ``conformance``).

The correctness contract is the paper's own guarantee, checked against
implementation-independent oracles (``repro.testing.oracle``): brute-force
exact k-NN in float64 numpy, plus the per-query ``(1/δ)`` approximation
bound that Theorem 1 proves for *any* greedy search on a δ-EMG.  No engine
is ever compared against another engine — parity between two approximate
implementations is circular and cannot catch a shared bug.

Layers:

* **δ-bound conformance** — every engine (beam search, faithful-prune
  variant, Alg.-5 probing, AGS) × backend × beam_width combination must
  satisfy ``returned_dist ≤ (1/δ)·d*`` for every query at every rank,
  against an exact Algorithm-2 build with known construction δ.
* **Honesty** — returned distances must *be* the true Euclidean distances
  of the returned ids (an engine must not be able to pass the bound by
  misreporting), ids must be valid and duplicate-free, dists sorted.
* **Metamorphic invariants** — corpus-row permutation leaves the bound
  intact (the oracle is permutation-equivariant), an injected duplicate
  point is found at distance 0, and a query equal to a corpus point
  returns distance 0 at rank 1.
* **Randomized corpora** — a parametrized seed sweep locally plus
  hypothesis-driven seeds in CI (``REPRO_CONFORMANCE_SEED`` rotates the
  base seed across the CI matrix).
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BuildParams,
    SearchParams,
    ags_search,
    build_emqg,
    build_exact,
    probing_search,
    search,
)
from repro.core.emqg import from_graph
from repro.serve import AnnServer
from repro.testing import oracle as oracle_mod
from repro.testing.oracle import check_delta_bound, exact_knn, recall_at_k

from conftest import gmm

pytestmark = pytest.mark.conformance

DELTA = 0.2          # construction δ — bound factor 1/δ = 5
K = 5


def _make_params(beam_width: int, l_max: int = 32,
                 max_hops: int = 256) -> SearchParams:
    return SearchParams(k=K, l0=8, l_max=l_max, alpha=1.2, adaptive=True,
                        max_hops=max_hops, beam_width=beam_width)


def _build(seed: int, n: int = 400, d: int = 16):
    """Exact Alg.-2 δ-EMG over a clustered corpus, plus queries + oracle."""
    base = gmm(n, d, 8, seed=seed)
    queries = gmm(16, d, 8, seed=seed + 1)
    graph = build_exact(jnp.asarray(base), delta=DELTA)
    oracle_d, oracle_i = exact_knn(base, queries, K)
    return base, queries, graph, oracle_d, oracle_i


@pytest.fixture(scope="module")
def fix(conformance_seed):
    base, queries, graph, oracle_d, oracle_i = _build(conformance_seed)
    return {"base": base, "queries": queries, "graph": graph,
            "emqg": from_graph(graph), "oracle_d": oracle_d,
            "oracle_i": oracle_i}


def _run(engine: str, fix, q, params: SearchParams, backend: str):
    if engine == "beam":
        return search(fix["graph"], q, params, backend=backend)
    if engine == "faithful":
        return search(fix["graph"], q, params, faithful_prune=True,
                      backend=backend)
    if engine == "probing":
        return probing_search(fix["emqg"], q, params, backend=backend)
    if engine == "ags":
        return ags_search(fix["emqg"], q, params, backend=backend)
    raise ValueError(engine)


ENGINES = ("beam", "faithful", "probing", "ags")


def _assert_conformant(res, fix, base=None):
    """δ-bound + honesty checks against the brute-force oracle."""
    base = fix["base"] if base is None else base
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    n = base.shape[0]
    assert ((ids >= 0) & (ids < n)).all()
    for row in ids:
        assert len(set(row.tolist())) == len(row)
    assert (np.diff(dists, axis=1) >= -1e-5).all()
    # honesty: reported distances are the true distances of the returned ids
    true = np.linalg.norm(
        base[ids.ravel()].reshape(ids.shape + (-1,))
        - np.asarray(fix["queries"])[:, None, :], axis=-1)
    np.testing.assert_allclose(dists, true, rtol=1e-4, atol=1e-4)
    # the paper's guarantee, per query, per rank
    assert check_delta_bound(dists, fix["oracle_d"], DELTA) is None


# ---------------------------------------------------------------------------
# δ-bound conformance: every engine × backend × beam_width combination.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("beam_width", [1, 4])
def test_delta_bound_jnp(fix, engine, beam_width):
    q = jnp.asarray(fix["queries"])
    res = _run(engine, fix, q, _make_params(beam_width), backend="jnp")
    _assert_conformant(res, fix)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("backend", ["kernel", "kernel_tiled"])
def test_delta_bound_kernel_backends(fix, engine, backend):
    """Pallas gather+L2 backends (interpret mode on CPU — kept small: the
    bound must hold on the kernel path, not just the XLA reference)."""
    q = jnp.asarray(fix["queries"][:4])
    res = _run(engine, fix, q,
               _make_params(beam_width=2, l_max=16, max_hops=96),
               backend=backend)
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    assert ((ids >= 0) & (ids < fix["base"].shape[0])).all()
    assert check_delta_bound(dists, fix["oracle_d"][:4], DELTA) is None


def test_adaptive_alpha_tightens_bound(fix):
    """Queries whose α-rule actually fired (not saturated) carry the
    tighter 1/(δ·α) bound of Algorithm 3."""
    q = jnp.asarray(fix["queries"])
    p = _make_params(beam_width=1)
    res = search(fix["graph"], q, p, backend="jnp")
    sat = np.asarray(res.saturated)
    if (~sat).any():
        assert check_delta_bound(np.asarray(res.dists)[~sat],
                                 fix["oracle_d"][~sat], DELTA,
                                 alpha=p.alpha) is None


def test_ags_rerank_recall_floor(fix):
    """AGS guides the walk with approximate distances, so beyond the bound
    its exact rerank should land most of the true neighbors here."""
    q = jnp.asarray(fix["queries"])
    res = ags_search(fix["emqg"], q, _make_params(beam_width=1))
    assert recall_at_k(np.asarray(res.ids), fix["oracle_i"]) >= 0.6
    # counters split correctly: traversal is approximate, rerank exact
    assert (np.asarray(res.n_approx_comps) > 0).all()
    assert (np.asarray(res.n_dist_comps) >= K).all()


# ---------------------------------------------------------------------------
# Metamorphic invariants.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_corpus_permutation_keeps_bound(fix, conformance_seed, engine):
    """Relabeling corpus rows changes ids but not geometry: the oracle
    distances are permutation-invariant and the bound must still hold on
    an index built from the permuted corpus."""
    rng = np.random.default_rng(conformance_seed + 100)
    perm = rng.permutation(fix["base"].shape[0])
    base_p = fix["base"][perm]
    graph_p = build_exact(jnp.asarray(base_p), delta=DELTA)
    fix_p = {"base": base_p, "queries": fix["queries"], "graph": graph_p,
             "emqg": from_graph(graph_p), "oracle_d": fix["oracle_d"]}
    q = jnp.asarray(fix["queries"])
    res = _run(engine, fix_p, q, _make_params(beam_width=1), backend="jnp")
    _assert_conformant(res, fix_p)


def test_duplicate_point_found_at_zero(conformance_seed):
    """Injecting an exact duplicate of a corpus row must not break the
    index, and querying that point returns distance 0 at rank 1."""
    base = gmm(200, 12, 6, seed=conformance_seed + 7)
    dup_row = base[17]
    base = np.concatenate([base, dup_row[None, :]], axis=0)
    graph = build_exact(jnp.asarray(base), delta=DELTA)
    q = jnp.asarray(dup_row[None, :])
    for engine, idx in (("beam", graph), ("probing", from_graph(graph))):
        run = search if engine == "beam" else probing_search
        res = run(idx, q, _make_params(beam_width=1), backend="jnp")
        assert float(np.asarray(res.dists)[0, 0]) < 1e-3, engine
        assert int(np.asarray(res.ids)[0, 0]) in (17, 200), engine


@pytest.mark.parametrize("engine", ENGINES)
def test_query_equals_corpus_point(fix, conformance_seed, engine):
    """q ∈ corpus ⇒ d* = 0, so the (1/δ) bound forces the engine to return
    that exact point (distance 0) at rank 1."""
    rng = np.random.default_rng(conformance_seed + 3)
    pick = rng.choice(fix["base"].shape[0], size=8, replace=False)
    q = jnp.asarray(fix["base"][pick])
    fix_q = dict(fix, queries=fix["base"][pick],
                 oracle_d=exact_knn(fix["base"], fix["base"][pick], K)[0])
    res = _run(engine, fix_q, q, _make_params(beam_width=1), backend="jnp")
    dists = np.asarray(res.dists)
    ids = np.asarray(res.ids)
    assert (dists[:, 0] < 1e-3).all()
    np.testing.assert_allclose(fix["base"][ids[:, 0]], fix["base"][pick],
                               rtol=1e-5, atol=1e-5)
    _assert_conformant(res, fix_q)


# ---------------------------------------------------------------------------
# δ-EMQG as it is served: Alg. 4 with degree alignment and RaBitQ codes,
# behind ``AnnServer``, which runs ``probing_search`` for an ``EMQGIndex``.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def emqg_served(conformance_seed):
    """Alg. 4's graph is only approximately δ-monotone: at build widths
    under these (M=16 or L=64 at d=16-32) the exact engine itself misses
    some corpus points on this corpus, so the case would not isolate the
    quantized path.  Here the exact engine finds them on seeds 0-2."""
    base = gmm(1536, 16, 16, seed=conformance_seed + 41)
    held_out = gmm(48, 16, 16, seed=conformance_seed + 42)
    pick = np.random.default_rng(conformance_seed + 43).choice(
        base.shape[0], size=48, replace=False)
    bp = BuildParams(max_degree=32, beam_width=100, t=32, iters=2,
                     delta=DELTA, block=512, align_degree=True)
    index = build_emqg(base, bp, key=jax.random.PRNGKey(conformance_seed))
    return {"base": base, "index": index,
            "queries": {"corpus_point": base[pick], "held_out": held_out}}


@pytest.mark.parametrize("kind", ["corpus_point", "held_out"])
def test_emqg_served_keeps_bound(emqg_served, kind):
    """A δ-EMQG build served by ``AnnServer`` answers every query within
    ``1/δ`` of float64 brute force, with exact distances, at every rank."""
    base, qs = emqg_served["base"], emqg_served["queries"][kind]
    srv = AnnServer(emqg_served["index"], _make_params(beam_width=1),
                    max_batch=16, buckets=(16,))
    srv.submit_many(qs)
    out = srv.drain()
    res = SimpleNamespace(ids=np.stack([i for i, _ in out]),
                          dists=np.stack([d for _, d in out]))
    fix_q = {"base": base, "queries": qs,
             "oracle_d": exact_knn(base, qs, K)[0]}
    _assert_conformant(res, fix_q)
    if kind == "corpus_point":
        assert (res.dists[:, 0] < 1e-3).all()


# ---------------------------------------------------------------------------
# Randomized corpora.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("offset", [11, 29])
def test_randomized_corpora_sweep(conformance_seed, offset):
    """Fresh corpus + queries per seed; bound must hold for the beam and
    faithful-prune engines (local, hypothesis-free version of the sweep)."""
    base, queries, graph, oracle_d, _ = _build(conformance_seed + offset,
                                               n=256, d=12)
    q = jnp.asarray(queries)
    for faithful in (False, True):
        res = search(graph, q, _make_params(beam_width=1),
                     faithful_prune=faithful, backend="jnp")
        assert check_delta_bound(np.asarray(res.dists), oracle_d,
                                 DELTA) is None


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**20))
def test_property_delta_bound_random_corpus(seed):
    """Hypothesis-driven corpora (CI): any seed, same guarantee.  Fixed
    shapes keep jit cache hits across examples."""
    base, queries, graph, oracle_d, _ = _build(seed, n=160, d=8)
    res = search(graph, jnp.asarray(queries),
                 _make_params(beam_width=2, l_max=24, max_hops=128),
                 backend="jnp")
    assert check_delta_bound(np.asarray(res.dists), oracle_d, DELTA) is None


# ---------------------------------------------------------------------------
# Oracle self-checks (the oracle must be trustworthy before it judges).
# ---------------------------------------------------------------------------

def test_oracle_permutation_equivariant(conformance_seed):
    base = gmm(100, 8, 4, seed=conformance_seed + 5)
    queries = gmm(6, 8, 4, seed=conformance_seed + 6)
    d0, i0 = exact_knn(base, queries, 4)
    perm = np.random.default_rng(0).permutation(100)
    d1, i1 = exact_knn(base[perm], queries, 4)
    np.testing.assert_allclose(d0, d1, rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(perm[i1], i0)


@pytest.mark.parametrize("block_elems", [1, 200, 1 << 24])
def test_oracle_blocked_equals_dense(conformance_seed, block_elems,
                                     monkeypatch):
    """Blocking over queries and corpus rows changes nothing: same float64
    distances, same ids, ties (planted duplicate rows) to the lower id."""
    base = gmm(120, 8, 4, seed=conformance_seed + 7).astype(np.float64)
    base[50] = base[3]
    base[90] = base[3]
    queries = np.concatenate([gmm(5, 8, 4, seed=conformance_seed + 8),
                              base[3:4]])
    d2 = np.sum((queries[:, None, :] - base[None, :, :]) ** 2, axis=-1)
    dense_i = np.argsort(d2, axis=1, kind="stable")[:, :6]
    dense_d = np.sqrt(np.take_along_axis(d2, dense_i, axis=1))
    monkeypatch.setattr(oracle_mod, "BLOCK_ELEMS", block_elems)
    d, i = exact_knn(base, queries, 6)
    np.testing.assert_array_equal(i, dense_i)
    np.testing.assert_array_equal(d, dense_d)
    assert list(i[-1, :3]) == [3, 50, 90]


def test_oracle_detects_violation():
    """check_delta_bound must actually fire on a planted violation."""
    oracle = np.full((2, 3), 1.0)
    good = np.full((2, 3), 1.0 / DELTA * 0.99)
    bad = good.copy()
    bad[1, 2] = 1.0 / DELTA * 1.05
    assert check_delta_bound(good, oracle, DELTA) is None
    msg = check_delta_bound(bad, oracle, DELTA)
    assert msg is not None and "query 1 rank 2" in msg
