"""What the program marks in a profile (``harness/program_trace.py``): the
scope map of a compiled search, the idle split by nested program spans,
the reduction of a small trace recorded on a TPU v5e
(``make_program_trace_fixture.py``: three batches of a few scoped hop
iterations, served with mirrored ``serve.*`` spans), and the seven metric
readers that read the hop phases, the lock-step counters and the serve
spans."""

import re
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import peaks, program_trace, spec, trace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data_program"
HOP_SCOPES = {"hop.select", "hop.expand", "hop.visited", "hop.distance",
              "hop.merge", "hop.transition"}
SERVE_SPANS = ("serve.put", "serve.launch", "serve.fetch")


def _loop_instructions(hlo: str):
    """The instructions of every while loop's body and condition that run
    on the device."""
    comps = program_trace.parse(hlo)
    loops = re.findall(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", hlo)
    assert loops
    return [ins for pair in loops for c in pair for ins in comps[c]
            if ins["op"] not in program_trace.INERT]


@pytest.fixture(scope="module")
def tiny_search_hlo():
    import jax.numpy as jnp

    from repro.core import SearchParams, build_exact, search

    rng = np.random.default_rng(2)
    base = rng.normal(size=(200, 8)).astype(np.float32)
    with pytest.warns(UserWarning):          # degree cap on a dense corpus
        graph = build_exact(base, delta=0.15, max_degree=8)
    p = SearchParams(k=5, l0=8, l_max=32, alpha=1.3, adaptive=True)
    return search.lower(graph, jnp.zeros((8, 8), jnp.float32), p,
                        backend="jnp").compile().as_text()


def test_scope_map_covers_the_loop(tiny_search_hlo):
    """Every op of the compiled loop (B=8, CPU) falls in one of the six
    hop scopes, and each scope holds some."""
    scope_of = program_trace.scope_map(tiny_search_hlo)
    loop = _loop_instructions(tiny_search_hlo)
    assert {scope_of.get(i["name"]) for i in loop} == HOP_SCOPES


def test_scope_of_op_name_innermost():
    f = program_trace.scope_of_op_name
    assert f("jit(search)/while/body/hop.merge/hop.visited/scatter") \
        == "hop.visited"
    assert f("jit(search)/while/body/hop.select/jit(take_along_axis)/gather"
             ) == "hop.select"
    assert f("jit(search)/sqrt") is None


def test_nest_and_split():
    pieces = program_trace.nest([(0, 100, "serve.batch"),
                                 (10, 40, "serve.device_execute"),
                                 (10, 15, "serve.put"),
                                 (15, 35, "serve.launch"),
                                 (35, 40, "serve.fetch"),
                                 (50, 120, "serve.merge")])
    assert pieces == [(0, 10, "serve.batch"), (10, 15, "serve.put"),
                      (15, 35, "serve.launch"), (35, 40, "serve.fetch"),
                      (40, 50, "serve.batch"), (50, 100, "serve.merge")]
    starts = [s for s, _, _ in pieces]
    assert program_trace.split(pieces, starts, 12, 110) == [
        ("serve.put", 12, 15), ("serve.launch", 15, 35),
        ("serve.fetch", 35, 40), ("serve.batch", 40, 50),
        ("serve.merge", 50, 100), (None, 100, 110)]
    assert program_trace.split(pieces, starts, 200, 210) == [
        (None, 200, 210)]


@pytest.fixture(scope="module")
def recorded():
    pd = trace.load(str(FIXTURE))
    red = trace.reduce(pd)
    scope_of = program_trace.scope_map(
        (FIXTURE / "program.hlo").read_text())
    red.update(program_trace.reduce(pd, scope_of))
    return red, scope_of


def test_recorded_scope_map(recorded):
    """The served program as the chip compiled it: its loop's ops all fall
    in the six scopes, the distance kernel under ``hop.distance``."""
    _, scope_of = recorded
    hlo = (FIXTURE / "program.hlo").read_text()
    loop = _loop_instructions(hlo)
    assert {scope_of.get(i["name"]) for i in loop} == HOP_SCOPES
    kernel = [i["name"] for i in loop
              if i["name"].startswith("gather_l2_tiled")]
    assert kernel and all(scope_of[k] == "hop.distance" for k in kernel)


def test_recorded_scopes(recorded):
    """Device self time by scope: each phase ran, and together the scopes
    hold most of the busy time (the rest is the set-up before the loop and
    the answers after it)."""
    red, _ = recorded
    s = red["scopes"]
    assert set(s) == HOP_SCOPES
    assert all(v > 0 for v in s.values())
    assert 0.5 * red["busy_s"] < sum(s.values()) <= red["busy_s"] + 1e-9


def test_recorded_idle_by_span(recorded):
    """Idle time split by the innermost mirrored span, else the harness's
    label: it sums to the window's idle time, as the labels' split does,
    and the serve spans take the idle time inside ``device_execute``."""
    red, _ = recorded
    idle = red["idle_by_span"]
    total = red["window_s"] - red["busy_s"]
    assert abs(sum(idle.values()) - total) < 1e-6
    assert abs(sum(v for _, v in red["idle_gaps"]) - total) < 1e-6
    assert all(idle.get(k, 0) > 0 for k in SERVE_SPANS)
    assert idle.get("generator_wait", 0) > 0.01
    labelled = dict(red["idle_gaps"])["device_execute"]
    assert idle.get("device_execute", 0.0) < 0.5 * labelled


def _reader(name):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                            "test_metric_" + name.replace(".", "_"))


def test_trace_readers_read_the_recorded_trace(recorded):
    """The four readers of the scopes and the idle split each read a
    number from the recorded trace, beside counters and spans as a run
    would give them."""
    from repro.obs import MetricsRegistry, Tracer

    red, _ = recorded
    reg, tr = MetricsRegistry(), Tracer()
    reg.counter("search_dist_comps_total").inc(3 * 8 * 50)
    for _ in range(3):
        tr.end_span(tr.start_span("serve.batch"))
    cell = SimpleNamespace(config={"dim": 128})
    run = SimpleNamespace(trace=red, registry=reg, tracer=tr, cell=cell,
                          n_answers=24, peaks=peaks.peaks("TPU v5 lite"))
    got = {n: _reader(n).read(run) for n in (
        "hop_visited_share.bulk", "hop_merge_share.bulk",
        "hop_distance_roofline.bulk",
        "serve_transfer_idle_ms_per_batch.online")}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    assert got["hop_visited_share.bulk"] + got["hop_merge_share.bulk"] < 100
    assert got["hop_distance_roofline.bulk"] < 100
    no_marks = SimpleNamespace(**{**vars(run), "trace": {
        k: v for k, v in red.items() if k not in ("scopes", "idle_by_span")}})
    assert all(_reader(n).read(no_marks) is None for n in got)


def test_program_readers_read_a_run(small_serve_run):
    """The lock-step and batch-iteration readers read a served run's
    counters and spans, and nothing from a run without them."""
    run = small_serve_run
    use_b = _reader("lockstep_row_use.bulk").read(run)
    use_o = _reader("lockstep_row_use.online").read(run)
    p99 = _reader("batch_iters_p99.online").read(run)
    assert 0 < use_b == use_o <= 100
    assert p99 >= 1
    from repro.obs import MetricsRegistry, Tracer

    empty = SimpleNamespace(registry=MetricsRegistry(), tracer=Tracer())
    for n in ("lockstep_row_use.bulk", "lockstep_row_use.online",
              "batch_iters_p99.online"):
        assert _reader(n).read(empty) is None


@pytest.fixture(scope="module")
def small_serve_run():
    from repro.core import SearchParams, build_exact
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve import AnnServer

    rng = np.random.default_rng(3)
    base = rng.normal(size=(200, 8)).astype(np.float32)
    with pytest.warns(UserWarning):
        graph = build_exact(base, delta=0.15, max_degree=8)
    reg, tr = MetricsRegistry(), Tracer()
    srv = AnnServer(graph, SearchParams(k=5, l0=8, l_max=32), max_batch=16,
                    buckets=(16,), metrics=reg, tracer=tr)
    srv.submit_many(rng.normal(size=(40, 8)).astype(np.float32))
    srv.drain()
    return SimpleNamespace(registry=reg, tracer=tr)
