"""Property tests for the packed visited bitset (``core/bitset.py``).

The bitset is the beam engine's dedup primitive and — since the faithful
Alg.-3 prune — also supports clearing (pruned-unexpanded candidates must be
able to re-enter the search).  Hypothesis drives randomized set/clear/test
round-trips against a plain Python-set model; deterministic versions of the
same invariants run even when hypothesis is absent (the compat shim turns
``@given`` tests into skips, and the clear op is load-bearing for
``faithful_prune`` so it must be covered unconditionally).

CI selects the ``ci`` hypothesis profile (conftest): derandomized, bounded
examples.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bitset import (
    bitset_clear,
    bitset_make,
    bitset_set,
    bitset_test,
    bitset_words,
    unique_per_row,
)

N = 200     # id space for the property tests (spans multiple uint32 words)


def _row(ids):
    """int32[1, K] row from a python list (pad-free)."""
    return jnp.asarray(np.asarray(ids, np.int32)[None, :])


# ---------------------------------------------------------------------------
# Deterministic invariants (always run).
# ---------------------------------------------------------------------------

def test_clear_inverts_set():
    ids = _row([0, 31, 32, 63, 64, 199])
    bits0 = bitset_make(1, N)
    bits1 = bitset_set(bits0, ids)
    assert np.asarray(bitset_test(bits1, ids)).all()
    bits2 = bitset_clear(bits1, ids)
    np.testing.assert_array_equal(np.asarray(bits2), np.asarray(bits0))
    assert not np.asarray(bitset_test(bits2, ids)).any()


def test_clear_subset_leaves_rest():
    bits = bitset_set(bitset_make(1, N), _row([3, 5, 7, 64, 65]))
    bits = bitset_clear(bits, _row([5, 64, -1]))
    got = np.asarray(bitset_test(bits, _row([3, 5, 7, 64, 65])))[0]
    assert got.tolist() == [True, False, True, False, True]


def test_clear_unset_bits_is_noop():
    bits = bitset_set(bitset_make(1, N), _row([10, 20]))
    bits2 = bitset_clear(bits, _row([11, 21, 199]))
    np.testing.assert_array_equal(np.asarray(bits2), np.asarray(bits))


def test_clear_invalid_ids_noop():
    bits = bitset_set(bitset_make(1, N), _row([42]))
    bits2 = bitset_clear(bits, _row([-1, -7]))
    np.testing.assert_array_equal(np.asarray(bits2), np.asarray(bits))


def test_clear_per_row_independent():
    ids = jnp.asarray([[1, 33], [1, 33]], jnp.int32)
    bits = bitset_set(bitset_make(2, N), ids)
    bits = bitset_clear(bits, jnp.asarray([[1, -1], [-1, 33]], jnp.int32))
    got = np.asarray(bitset_test(bits, ids))
    assert got.tolist() == [[False, True], [True, False]]


def test_words_cover_id_space():
    for n in (1, 31, 32, 33, 200, 1024):
        assert bitset_words(n) * 32 >= n
        assert (bitset_words(n) - 1) * 32 < n


# ---------------------------------------------------------------------------
# Hypothesis properties (CI: derandomized profile; local: skip w/o dep).
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(ids=st.lists(st.integers(0, N - 1), min_size=1, max_size=40,
                    unique=True))
def test_set_test_roundtrip_vs_model(ids):
    """Members test True, non-members False — exactly the python-set model."""
    bits = bitset_set(bitset_make(1, N), _row(ids))
    model = set(ids)
    probe = list(range(0, N, 3)) + ids
    got = np.asarray(bitset_test(bits, _row(probe)))[0]
    assert got.tolist() == [v in model for v in probe]


@settings(max_examples=50, deadline=None)
@given(ids=st.lists(st.integers(0, N - 1), min_size=1, max_size=40,
                    unique=True),
       drop=st.sets(st.integers(0, N - 1), max_size=20))
def test_set_clear_vs_model(ids, drop):
    """set(A) then clear(B) ⇔ membership A \\ B (clearing absent ids is a
    no-op, mirroring a prune of a never-seen candidate)."""
    bits = bitset_set(bitset_make(1, N), _row(ids))
    bits = bitset_clear(bits, _row(sorted(drop)))
    model = set(ids) - drop
    probe = list(range(N))
    got = np.asarray(bitset_test(bits, _row(probe)))[0]
    assert got.tolist() == [v in model for v in probe]


@settings(max_examples=50, deadline=None)
@given(ids=st.lists(st.integers(-1, N - 1), min_size=1, max_size=60))
def test_unique_per_row_vs_np_unique(ids):
    """Valid output entries == np.unique of the valid inputs, ascending,
    with the tail padded INVALID."""
    arr = _row(ids)
    out = np.asarray(unique_per_row(arr, arr >= 0))[0]
    valid = out[out >= 0]
    expect = np.unique(np.asarray([v for v in ids if v >= 0], np.int32))
    np.testing.assert_array_equal(valid, expect)
    if valid.size:
        assert (np.diff(valid) > 0).all()
    assert (out[valid.size:] == -1).all()


@settings(max_examples=30, deadline=None)
@given(ids=st.lists(st.integers(0, N - 1), min_size=1, max_size=30,
                    unique=True))
def test_clear_is_involution_boundary(ids):
    """set→clear→set→clear lands back at empty: add/drop cycles cannot
    leak bits (the faithful-prune loop does exactly this per hop)."""
    empty = bitset_make(1, N)
    row = _row(ids)
    bits = bitset_clear(bitset_set(empty, row), row)
    bits = bitset_clear(bitset_set(bits, row), row)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(empty))


def test_hypothesis_status_reported():
    """hypothesis is a hard test dependency: the property tests above are
    real hypothesis tests, never skips."""
    assert hasattr(test_unique_per_row_vs_np_unique, "hypothesis")
