"""Property tests for the occlusion geometry (Def. 9, Lemma 1)."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import geometry

DIMS = st.integers(min_value=2, max_value=16)


def _rand_vec(rng, d, scale=1.0):
    return rng.normal(size=(d,)).astype(np.float32) * scale


@settings(max_examples=200, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=DIMS,
       delta=st.floats(0.01, 0.9))
def test_lemma1_occluder_always_progresses(seed, d, delta):
    """Lemma 1: for w ∈ Occlusionδ(u,v) and any q with d(q,v) < δ·d(q,u),
    d(q,w) < d(q,u).  Sample w by rejection inside the region and q inside
    the navigable ball."""
    rng = np.random.default_rng(seed)
    u = _rand_vec(rng, d)
    v = u + _rand_vec(rng, d, 0.7) + 1e-2
    d_uv = float(np.linalg.norm(u - v))

    # rejection-sample an occluder w
    w = None
    for _ in range(300):
        cand = u + (v - u) * rng.uniform(0.1, 0.9) + _rand_vec(rng, d, 0.2 * d_uv)
        if bool(geometry.in_occlusion_region(
                jnp.asarray(cand), jnp.asarray(u), jnp.asarray(v), delta)):
            w = cand
            break
    if w is None:
        return  # region too small at this δ/geometry — vacuous draw

    # sample q in the open ball B(v/(1−δ²), δ‖v‖/(1−δ²)) (coords u at origin)
    c = u + (v - u) / (1 - delta**2)
    R = delta * d_uv / (1 - delta**2)
    dirn = _rand_vec(rng, d)
    dirn /= np.linalg.norm(dirn) + 1e-12
    q = c + dirn * R * rng.uniform(0.0, 0.999)
    # guard: the ball characterization must hold
    if not bool(np.linalg.norm(q - v) < delta * np.linalg.norm(q - u)):
        return

    assert np.linalg.norm(q - w) < np.linalg.norm(q - u) + 1e-6


@settings(max_examples=100, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=DIMS)
def test_delta_zero_limit_is_mrng_lune(seed, d):
    """As δ → 0 the region converges to the MRNG lune."""
    rng = np.random.default_rng(seed)
    u, v, x = _rand_vec(rng, d), _rand_vec(rng, d), _rand_vec(rng, d)
    d2_uv = float(np.sum((u - v) ** 2))
    d2_xu = float(np.sum((x - u) ** 2))
    d2_xv = float(np.sum((x - v) ** 2))
    tiny = bool(geometry.occludes_delta(d2_uv, d2_xu, d2_xv, 1e-7))
    lune = bool(geometry.occludes_mrng(d2_uv, d2_xu, d2_xv))
    # δ>0 region ⊆ lune, and at δ→0 they agree except a measure-zero boundary
    if tiny:
        assert lune
    if lune and not tiny:
        # must be a boundary case: d²(x,v) within ε of d²(u,v)
        assert d2_xv + 2e-7 * np.sqrt(d2_uv * d2_xu) >= d2_uv - 1e-4


@settings(max_examples=100, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=DIMS,
       d1=st.floats(0.05, 0.5), d2=st.floats(0.5, 0.95))
def test_occlusion_region_monotone_in_delta(seed, d, d1, d2):
    """Larger δ shrinks the region: Occlusion_{δ2} ⊆ Occlusion_{δ1}, δ1<δ2."""
    rng = np.random.default_rng(seed)
    u, v, x = _rand_vec(rng, d), _rand_vec(rng, d), _rand_vec(rng, d)
    args = (jnp.sum((u - v) ** 2), jnp.sum((x - u) ** 2), jnp.sum((x - v) ** 2))
    lo, hi = min(d1, d2), max(d1, d2)
    if bool(geometry.occludes_delta(*[jnp.asarray(a) for a in args], hi)):
        assert bool(geometry.occludes_delta(*[jnp.asarray(a) for a in args], lo))


def test_adaptive_deltas_schedule():
    d2 = jnp.asarray([0.25, 1.0, 4.0, 16.0])  # dists 0.5, 1, 2, 4
    deltas = geometry.adaptive_deltas(d2, t=2)  # d_(t) = 1.0
    np.testing.assert_allclose(np.asarray(deltas), [0.5, 0.0, -1.0, -3.0],
                               atol=1e-6)


def test_select_neighbors_first_always_kept():
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(20, 8)).astype(np.float32)
    u = vecs[0]
    cand = vecs[1:]
    d2 = np.sum((cand - u) ** 2, axis=1)
    order = np.argsort(d2)
    ids, count = geometry.select_neighbors(
        jnp.asarray(u), jnp.asarray(cand[order]), jnp.asarray(d2[order]),
        jnp.asarray(order.astype(np.int32) + 1),
        jnp.full((19,), 0.05), rule="delta_emg", max_keep=8)
    ids = np.asarray(ids)
    assert int(count) >= 1
    assert ids[0] == order[0] + 1  # nearest candidate always kept


def test_select_neighbors_rejects_self_and_invalid():
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(10, 4)).astype(np.float32)
    u = vecs[0]
    cand = np.concatenate([u[None], vecs[1:]])
    d2 = np.sum((cand - u) ** 2, axis=1)
    ids_in = np.arange(10, dtype=np.int32)
    ids_in[5] = -1
    ids, count = geometry.select_neighbors(
        jnp.asarray(u), jnp.asarray(cand), jnp.asarray(d2),
        jnp.asarray(ids_in), jnp.full((10,), 0.05), max_keep=8)
    ids = np.asarray(ids)[: int(count)]
    assert 0 not in ids.tolist()      # self (d²=0) excluded
    assert -1 not in ids.tolist()
