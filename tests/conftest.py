"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the real
single-CPU device; multi-device tests spawn subprocesses that set
--xla_force_host_platform_device_count themselves."""

import os

import numpy as np
import pytest

from hypothesis import HealthCheck, settings as _hyp_settings

# CI hypothesis profile: derandomized (fixed seed) with bounded examples so
# property tests are deterministic and time-boxed; select another profile
# via HYPOTHESIS_PROFILE.
_hyp_settings.register_profile(
    "ci", max_examples=25, deadline=None, derandomize=True,
    suppress_health_check=[HealthCheck.too_slow])
_hyp_settings.register_profile("dev", max_examples=50, deadline=None)
_hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))


def gmm(n, d, k_clusters, seed, scale=0.35):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k_clusters, d))
    asg = rng.integers(0, k_clusters, n)
    return (centers[asg] + scale * rng.normal(size=(n, d))).astype(np.float32)


@pytest.fixture(scope="session")
def small_corpus():
    """Clustered corpus + queries + brute-force ground truth (k=10)."""
    from repro.core.distances import brute_force_knn

    base = gmm(1200, 24, 24, seed=0)
    queries = gmm(64, 24, 24, seed=1)
    gt_d, gt_i = brute_force_knn(queries, base, 10)
    return {"base": base, "queries": queries, "gt_d": gt_d, "gt_i": gt_i}


def recall_at_k(ids, gt_i, k):
    ids = np.asarray(ids)[:, :k]
    return float(np.mean([
        len(set(ids[i].tolist()) & set(gt_i[i, :k].tolist())) / k
        for i in range(ids.shape[0])
    ]))


@pytest.fixture(scope="session")
def fault_seed():
    """Seed for the fault-injection suite.  CI sweeps REPRO_FAULT_SEED over a
    matrix so deterministic fault schedules get exercised from several
    starting states; locally it defaults to 0."""
    return int(os.environ.get("REPRO_FAULT_SEED", "0"))


@pytest.fixture(scope="session")
def conformance_seed():
    """Seed for the oracle-based conformance suite's randomized corpora.
    CI sweeps REPRO_CONFORMANCE_SEED over a matrix; locally defaults to 0."""
    return int(os.environ.get("REPRO_CONFORMANCE_SEED", "0"))
