"""RaBitQ quantization: packing, estimator quality, error bounds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import rabitq


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 40),
       d=st.integers(2, 200))
def test_pack_unpack_roundtrip(seed, n, d):
    rng = np.random.default_rng(seed)
    bits = rng.random((n, d)) > 0.5
    packed = rabitq.pack_bits(jnp.asarray(bits))
    signs = np.asarray(rabitq.unpack_bits(packed, d))
    np.testing.assert_array_equal(signs > 0, bits)


def test_rotation_is_orthogonal():
    for d in (8, 64, 100):
        P = np.asarray(rabitq.random_rotation(d, jax.random.PRNGKey(0)))
        np.testing.assert_allclose(P @ P.T, np.eye(d), atol=1e-4)


def test_estimator_relative_error_small(small_corpus):
    base = small_corpus["base"]
    q = small_corpus["queries"][0]
    codes = rabitq.fit(jnp.asarray(base), jax.random.PRNGKey(0))
    ctx = rabitq.prepare_query(codes, jnp.asarray(q))
    ids = jnp.arange(400, dtype=jnp.int32)
    est = np.asarray(rabitq.estimate_sqdist(codes, ctx, ids))
    true = np.sum((base[:400] - q) ** 2, axis=1)
    rel = np.abs(est - true) / np.maximum(true, 1e-9)
    assert rel.mean() < 0.15          # d=24: O(1/√d) noise
    assert np.median(rel) < 0.12


def test_estimator_approaches_truth_with_dim():
    """Concentration: relative error shrinks ~1/√d."""
    rng = np.random.default_rng(0)
    errs = []
    for d in (16, 128, 512):
        base = rng.normal(size=(300, d)).astype(np.float32)
        q = rng.normal(size=(d,)).astype(np.float32)
        codes = rabitq.fit(jnp.asarray(base), jax.random.PRNGKey(1))
        ctx = rabitq.prepare_query(codes, jnp.asarray(q))
        est = np.asarray(rabitq.estimate_sqdist(
            codes, ctx, jnp.arange(300, dtype=jnp.int32)))
        true = np.sum((base - q) ** 2, axis=1)
        errs.append(float(np.mean(np.abs(est - true) / true)))
    assert errs[2] < errs[1] < errs[0]
    assert errs[2] < 0.04


def test_estimator_unbiased_over_rotations():
    """⟨o,q⟩ estimate is (approximately) unbiased: averaging estimates over
    independent rotations converges to the true value."""
    rng = np.random.default_rng(0)
    d = 48
    base = rng.normal(size=(50, d)).astype(np.float32)
    q = rng.normal(size=(d,)).astype(np.float32)
    true = np.sum((base - q) ** 2, axis=1)
    ests = []
    for s in range(24):
        codes = rabitq.fit(jnp.asarray(base), jax.random.PRNGKey(s))
        ctx = rabitq.prepare_query(codes, jnp.asarray(q))
        ests.append(np.asarray(rabitq.estimate_sqdist(
            codes, ctx, jnp.arange(50, dtype=jnp.int32))))
    mean_est = np.mean(ests, axis=0)
    rel_bias = np.abs(mean_est - true) / true
    single_rel = np.mean(np.abs(ests[0] - true) / true)
    assert rel_bias.mean() < single_rel  # averaging reduces error ⇒ low bias
    assert rel_bias.mean() < 0.05


def test_error_bound_coverage(small_corpus):
    """The ε₀=2.2 high-probability bound should cover ≳95% of cases
    (the paper's ε₀≈1.9 targets d ≥ 128; at d=24 the tail is fatter)."""
    base = small_corpus["base"]
    codes = rabitq.fit(jnp.asarray(base), jax.random.PRNGKey(2))
    covered, total = 0, 0
    for qi in range(16):
        q = small_corpus["queries"][qi]
        ctx = rabitq.prepare_query(codes, jnp.asarray(q))
        ids = jnp.arange(300, dtype=jnp.int32)
        est = np.asarray(rabitq.estimate_sqdist(codes, ctx, ids))
        bound = np.asarray(rabitq.estimator_error_bound(codes, ids, eps0=2.2))
        true = np.sum((base[:300] - q) ** 2, axis=1)
        nv = np.linalg.norm(base[:300] - np.asarray(codes.center)[None], axis=1)
        nq = float(np.linalg.norm(q - np.asarray(codes.center)))
        # |est_d² − true_d²| = 2·‖v−c‖·‖q−c‖·|est_cos − cos|
        slack = 2 * nv * nq * bound
        covered += int(np.sum(np.abs(est - true) <= slack + 1e-6))
        total += 300
    assert covered / total > 0.95


def test_invalid_ids_inf():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(20, 16)).astype(np.float32)
    codes = rabitq.fit(jnp.asarray(base), jax.random.PRNGKey(0))
    ctx = rabitq.prepare_query(codes, jnp.asarray(base[0]))
    est = rabitq.estimate_sqdist(codes, ctx,
                                 jnp.asarray([0, -1, 3], jnp.int32))
    assert bool(jnp.isinf(est[1])) and bool(jnp.isfinite(est[0]))


# ---------------------------------------------------------------------------
# Against the float64 reference (``repro.testing.rabitq_ref``), written from
# the estimator's formula.
# ---------------------------------------------------------------------------

def _np_unpack(words, d):
    """bit j of word w = dimension 32·w + j (numpy, not the program's)."""
    w = np.asarray(words, np.uint64)
    bits = (w[:, :, None] >> np.arange(32, dtype=np.uint64)) & 1
    return bits.reshape(w.shape[0], -1)[:, :d].astype(bool)


@pytest.mark.parametrize("shape", ["sift_like", "gaussian"])
def test_fit_and_estimate_match_float64_reference(shape):
    from repro.testing import rabitq_ref

    rng = np.random.default_rng(11)
    if shape == "sift_like":      # integers in [0, 255], a third zeros
        base = np.clip(np.rint(rng.normal(20, 30, (600, 128))), 0, 255)
        queries = np.clip(np.rint(rng.normal(20, 30, (8, 128))), 0, 255)
    else:
        base = rng.normal(size=(600, 24))
        queries = rng.normal(size=(8, 24))
    base, queries = base.astype(np.float32), queries.astype(np.float32)
    codes = rabitq.fit(jnp.asarray(base), jax.random.PRNGKey(3))
    ref = rabitq_ref.fit(base, np.asarray(codes.rotation))
    d = base.shape[1]

    # scalars: f32 sums of d terms against f64 read at most 3.2e-7 apart
    # here; rtol 2e-6 (about 16 f32 ulps) leaves room for another
    # summation order and no more
    np.testing.assert_allclose(np.asarray(codes.norms), ref.norms, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(codes.ip_xo), ref.ip_xo, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(codes.center), ref.center,
                               rtol=1e-5, atol=1e-5 * np.abs(ref.center).max())

    # codes: equal bit for bit, except where the residual's f32 rounding
    # (at most 1e-5 of the row's norm) can flip a coordinate's sign
    bits = _np_unpack(np.asarray(codes.codes), d)
    ambiguous = np.abs(ref.r) <= 1e-5 * ref.norms[:, None]
    assert (bits == ref.bits)[~ambiguous].all()
    exact_rows = ~ambiguous.any(axis=1)
    assert exact_rows.mean() > 0.95

    # estimates, on rows whose codes agree: the estimate's three terms are
    # each within f32 rounding of (‖r‖ + ‖r_q‖)² (read: at most 2.1e-7 of
    # it), so 2e-6 of that
    ids = np.flatnonzero(exact_rows).astype(np.int32)
    for q in queries:
        ctx = rabitq.prepare_query(codes, jnp.asarray(q))
        got = np.asarray(rabitq.estimate_sqdist(codes, ctx, jnp.asarray(ids)))
        want = rabitq_ref.estimate_sqdist(ref, q, ids)
        r_q = (q.astype(np.float64) - ref.center) @ ref.rotation.T
        scale = (ref.norms[ids] + np.linalg.norm(r_q)) ** 2
        assert (np.abs(got - want) <= 2e-6 * scale).all()
        # INVALID ids estimate +inf
        bad = rabitq.estimate_sqdist(codes, ctx, jnp.asarray([-1], jnp.int32))
        assert np.isinf(np.asarray(bad)).all()


def test_float64_reference_is_the_estimator():
    """The reference itself, on a case worked by hand: a vector whose
    residual lies on a sign pattern is estimated exactly (⟨x̄, o⟩ = 1)."""
    from repro.testing import rabitq_ref

    d = 4
    v = np.array([[1.0, 1.0, -1.0, -1.0], [-1.0, -1.0, 1.0, 1.0]])
    ref = rabitq_ref.fit(v, np.eye(d))
    np.testing.assert_allclose(ref.ip_xo, [1.0, 1.0])
    q = np.array([2.0, 0.0, 0.0, 1.0])
    est = rabitq_ref.estimate_sqdist(ref, q, [0, 1])
    np.testing.assert_allclose(est, np.sum((v - q) ** 2, axis=1))
