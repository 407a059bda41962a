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
