"""Ahead-of-time compiles of the serve path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse (block
layouts off the (8, 128) tiling rule, SMEM or VMEM overflow, casts Mosaic
does not lower).  Every case asserts that the Pallas kernel survived into
the compiled program (``tpu_custom_call``) rather than a jnp fallback.

Shapes are the paper's SIFT1M deployment (``configs/sift1m.py``): n=1M,
d=128, M=64, k=10, l_max=512, online batch 256 and bulk batch 4096.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SearchParams, probing_search
from repro.core.types import EMQGIndex, GraphIndex, RaBitQCodes
from repro.kernels.bitdot import ops as bitops
from repro.kernels.l2dist import ops as l2ops

N, D, M, K = 1_000_000, 128, 64, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_gather(sharding, B, K):
    return l2ops.gather_l2_tiled.lower(
        _sds((N, D), jnp.float32, sharding),
        _sds((B, K), jnp.int32, sharding),
        _sds((B, D), jnp.float32, sharding),
        interpret=False).compile()


@pytest.mark.parametrize("B", [256, 1024, 4096])
@pytest.mark.parametrize("W", [1, 4])
def test_gather_l2_tiled_compiles(one_chip, B, W):
    _assert_kernel(_compile_gather(one_chip, B, W * M))


@pytest.mark.parametrize("B", [256, 1024])
def test_gather_l2_tiled_start_call_compiles(one_chip, B):
    """The per-batch start call: one id per query, padded to 8 slots."""
    _assert_kernel(_compile_gather(one_chip, B, 1))


def test_bitdot_compiles(one_chip):
    m, words = 4096, D // 32
    compiled = bitops.bitdot.lower(
        _sds((m, words), jnp.uint32, one_chip),
        _sds((D,), jnp.float32, one_chip),
        interpret=False).compile()
    _assert_kernel(compiled)


def test_fused_estimate_compiles(one_chip):
    m, words = 4096, D // 32
    compiled = bitops.fused_estimate.lower(
        _sds((m, words), jnp.uint32, one_chip),
        _sds((m,), jnp.float32, one_chip),
        _sds((m,), jnp.float32, one_chip),
        _sds((D,), jnp.float32, one_chip),
        _sds((), jnp.float32, one_chip),
        dim=D, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.fixture(scope="module")
def probing_hlo(one_chip):
    """The whole served δ-EMQG program, compiled once: Algorithm 5 at B=256
    with the exact tier on ``gather_l2_tiled``.  This process's backend is
    the CPU, where the kernel wrapper picks interpret mode; steer it to the
    compiled kernel."""
    B = 256
    graph = GraphIndex(vectors=_sds((N, D), jnp.float32, one_chip),
                       neighbors=_sds((N, M), jnp.int32, one_chip),
                       medoid=_sds((), jnp.int32, one_chip),
                       kind="delta_emqg", delta=0.2)
    codes = RaBitQCodes(codes=_sds((N, D // 32), jnp.uint32, one_chip),
                        norms=_sds((N,), jnp.float32, one_chip),
                        ip_xo=_sds((N,), jnp.float32, one_chip),
                        rotation=_sds((D, D), jnp.float32, one_chip),
                        center=_sds((D,), jnp.float32, one_chip), dim=D)
    params = SearchParams(k=K, l0=K, l_max=512, alpha=1.2, adaptive=True,
                          max_hops=4096)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(l2ops, "_on_cpu", lambda: False)
        return probing_search.lower(
            EMQGIndex(graph=graph, codes=codes),
            _sds((B, D), jnp.float32, one_chip), params,
            backend="kernel_tiled").compile().as_text()


def test_probing_search_program_compiles(probing_hlo):
    assert "tpu_custom_call" in probing_hlo


def _loop_scopes(hlo: str):
    """{scope} of the benchmark's scope map over every op of the compiled
    program's loops, and the scopes of its ``gather_l2_tiled`` calls."""
    import re
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from harness import program_trace

    comps = program_trace.parse(hlo)
    scope_of = program_trace.scope_map(hlo)
    loop = [ins for pair in re.findall(
                r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", hlo)
            for c in pair for ins in comps[c]
            if ins["op"] not in program_trace.INERT]
    return ({scope_of.get(i["name"]) for i in loop},
            {scope_of[i["name"]] for i in loop
             if i["name"].startswith("gather_l2_tiled")})


HOP_SCOPES = {"hop.select", "hop.expand", "hop.visited", "hop.distance",
              "hop.merge", "hop.transition"}


def test_search_program_phases_scoped(one_chip, monkeypatch):
    """The served exact program (``search`` at the online batch, on
    ``gather_l2_tiled``): as the chip's compiler emits it, every op of its
    loop falls under one of the six ``hop.*`` named scopes in the
    benchmark's scope map, the kernel under ``hop.distance``."""
    from repro.core import search

    monkeypatch.setattr(l2ops, "_on_cpu", lambda: False)
    B = 256
    graph = GraphIndex(vectors=_sds((N, D), jnp.float32, one_chip),
                       neighbors=_sds((N, M), jnp.int32, one_chip),
                       medoid=_sds((), jnp.int32, one_chip),
                       kind="delta_emg", delta=0.2)
    params = SearchParams(k=K, l0=K, l_max=512, alpha=1.2, adaptive=True,
                          max_hops=4096)
    hlo = search.lower(graph, _sds((B, D), jnp.float32, one_chip), params,
                       backend="kernel_tiled").compile().as_text()
    assert "tpu_custom_call" in hlo
    scopes, kernel = _loop_scopes(hlo)
    assert scopes == HOP_SCOPES
    assert kernel == {"hop.distance"}


def test_probing_program_phases_scoped(probing_hlo):
    """The served δ-EMQG program as the chip's compiler emits it: every op
    of its loops falls under the six scopes or ``hop.estimate`` (the RaBitQ
    estimates), the exact kernel under ``hop.distance``."""
    scopes, kernel = _loop_scopes(probing_hlo)
    assert scopes == HOP_SCOPES | {"hop.estimate"}
    assert kernel == {"hop.distance"}


def test_search_kernel_calls_read_as_all_rows(one_chip, monkeypatch):
    """The bulk batch's program (``search`` at B=1,024), with the kernel and
    every ``hop.*`` scope in its loops: every ``gather_l2_tiled`` call in
    it, found by the benchmark's custom-call reader, has three operands
    (ids, the corpus, the queries) and is charged its own rows, B_s·K′
    gathered rows of d floats — the start call's [B, 1] ids padded to 8
    slots, and the [B_s, 64] of each stage of the compacting loop, B_s
    running down the ladder from B — so the kernel's roofline reads the
    same work whatever the kernel's blocking."""
    import re
    import sys
    from pathlib import Path

    from repro.core import lockstep, search

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from harness import kernels

    monkeypatch.setattr(l2ops, "_on_cpu", lambda: False)
    B = 1024
    graph = GraphIndex(vectors=_sds((N, D), jnp.float32, one_chip),
                       neighbors=_sds((N, M), jnp.int32, one_chip),
                       medoid=_sds((), jnp.int32, one_chip),
                       kind="delta_emg", delta=0.2)
    params = SearchParams(k=K, l0=K, l_max=512, alpha=1.2, adaptive=True,
                          max_hops=4096)
    hlo = search.lower(graph, _sds((B, D), jnp.float32, one_chip), params,
                       backend="kernel_tiled").compile().as_text()
    assert "tpu_custom_call" in hlo
    scopes, kernel = _loop_scopes(hlo)
    assert scopes == HOP_SCOPES
    assert kernel == {"hop.distance"}
    calls = kernels.custom_calls(hlo, "gather_l2_tiled")
    named = re.findall(r"^\s*%?(gather_l2_tiled[\w.\-]*) = \S+ custom-call\(",
                       hlo, re.M)
    assert calls and sorted(calls) == sorted(named)
    stages = lockstep.ladder(B)
    assert len(stages) > 1
    rows = set()
    for operands in calls.values():
        (ids_t, _), (base_t, base), (q_t, q) = operands
        assert (ids_t, base_t, q_t) == ("s32", "f32", "f32")
        assert base == (N, D) and q[0] in stages
        rows.add(kernels.gather_l2_tiled_work(operands)["flops"] // (3 * D))
    assert rows == {B * 8} | {b * M for b in stages}
