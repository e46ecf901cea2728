"""The paged scheduler's own spans (``launch.tracing``): recorded only
while a JAX profiler session collects, nested under one ``step`` per
call, ``compile`` on the first call of a program and ``dispatch`` after,
and per-request ``queued`` / ``staged`` spans keyed by rid."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs as cfglib
from repro.launch.scheduler import PagedContinuousBatchingServer
from repro.launch.tracing import Tracer
from repro.models.registry import get_model

CHILDREN = {"stage", "admit", "grow", "dispatch", "compile", "retire"}


def _server():
    cfg = cfglib.get_smoke_config("nemotron-4-15b")
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, PagedContinuousBatchingServer(
        cfg, params, num_slots=2, max_len=48, block_size=8, segment=4,
        prefill_chunk=8)


def _serve(srv, prompt, gen):
    rid = srv.submit(prompt, gen)
    while srv.load:
        srv.step()
    return rid


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two requests of the same shape, one after the other, served under
    a profiler session: the second meets only programs the first built."""
    cfg, srv = _server()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=13).astype(np.int32)
               for _ in range(2)]
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        first = _serve(srv, prompts[0], 6)
        mark = len(srv.tracer.spans)
        second = _serve(srv, prompts[1], 6)
    finally:
        jax.profiler.stop_trace()
    return srv, mark, (first, second), str(out)


def _root(spans, i):
    while spans[i].parent is not None:
        i = spans[i].parent
    return i


def test_every_child_span_nests_inside_a_step(traced):
    srv, _, _, _ = traced
    spans = srv.tracer.spans
    nested = [i for i, sp in enumerate(spans) if sp.rid is None]
    assert {spans[i].name for i in nested} >= {"step", "stage", "admit",
                                               "grow", "retire"}
    for i in nested:
        sp = spans[i]
        assert sp.end_ns is not None and sp.start_ns <= sp.end_ns
        if sp.name == "step":
            assert sp.parent is None
            continue
        assert sp.name in CHILDREN
        root = spans[_root(spans, i)]
        assert root.name == "step"
        parent = spans[sp.parent]
        assert parent.start_ns <= sp.start_ns <= sp.end_ns <= parent.end_ns


def test_first_call_of_a_key_compiles_and_a_repeat_dispatches(traced):
    srv, mark, _, _ = traced
    before = [sp.name for sp in srv.tracer.spans[:mark]]
    after = [sp.name for sp in srv.tracer.spans[mark:]]
    assert "compile" in before
    # the second request has the first's shapes: every program is built
    assert "compile" not in after
    assert after.count("dispatch") == before.count("dispatch") + before.count(
        "compile")
    # each program built ran once under a compile span
    assert before.count("compile") == srv.stats.compiles


def test_queued_and_staged_carry_the_rid_in_order(traced):
    srv, _, rids, _ = traced
    spans = srv.tracer.spans
    for rid in rids:
        mine = {sp.name: sp for sp in spans if sp.rid == rid}
        assert set(mine) == {"queued", "staged"}
        q, s = mine["queued"], mine["staged"]
        assert q.parent is None and s.parent is None
        assert q.start_ns <= q.end_ns <= s.start_ns <= s.end_ns


def test_compile_calls_equals_the_compile_spans(traced):
    srv, _, _, _ = traced
    compiles = sum(sp.name == "compile" for sp in srv.tracer.spans)
    assert srv.stats.compile_calls == compiles > 0


def test_spans_show_in_the_profiler_trace(traced):
    srv, _, _, out = traced
    (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events]
    steps = sum(sp.name == "step" for sp in srv.tracer.spans)
    assert names.count("serve.step") == steps
    assert names.count("serve.compile") == srv.stats.compile_calls


def test_no_profiler_session_records_nothing():
    cfg, srv = _server()
    rng = np.random.RandomState(1)
    _serve(srv, rng.randint(0, cfg.vocab_size, size=9).astype(np.int32), 5)
    assert srv.tracer.spans == [] and srv.tracer.dropped == 0
    assert srv.tracer._open == {}
    # the counters still count: every program built was called once
    assert srv.stats.compile_calls == srv.stats.compiles > 0
    # one shared no-op context when nothing collects
    assert srv.tracer.span("step") is srv.tracer.span("grow")


def test_spans_past_the_cap_are_counted_not_kept(tmp_path):
    tracer = Tracer()
    tracer.cap = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("step"):
            for _ in range(3):
                with tracer.span("dispatch"):
                    pass
        tracer.begin("queued", 7)
        tracer.end("queued", 7)
    finally:
        jax.profiler.stop_trace()
    assert [sp.name for sp in tracer.spans] == ["step", "dispatch",
                                                "dispatch"]
    assert tracer.dropped == 2
    assert all(sp.end_ns is not None for sp in tracer.spans)
