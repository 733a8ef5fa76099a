"""Spans inside ``engine.step()`` (repro.obs.events.Tracer.span): nesting
and parents, per-token stamps (``first_token`` + ``decode`` rids),
attended lengths, compile spans, the off path building nothing, the
profiler twins on the host plane of a ``jax.profiler`` trace, and the
Chrome export."""

import glob
import os

import jax
import numpy as np
import pytest

from repro import configs as CONFIGS
from repro.models import network as N
from repro.obs import Telemetry
from repro.obs import events as EV
from repro.obs.events import validate_chrome_trace
from repro.serving.engine import ContinuousEngine, Request

#: span types recorded inside a working step() call, each a child of one
#: of the types listed
PARENTS = {
    "admit_pass": {"step"},
    "chunk_batch": {"step"},
    "decode": {"step"},
    "prep": {"chunk_batch", "decode"},
    "launch": {"chunk_batch", "decode"},
    "sync": {"chunk_batch", "decode"},
    "retire": {"step"},
    "pool_admit": {"admit_pass"},
    "pool_register": {"retire"},
    "pool_release": {"retire"},
}
STEP_SPANS = set(PARENTS) | {"step"}


@pytest.fixture(scope="module")
def tiny():
    cfg = CONFIGS.get("qwen2_0_5b").scaled_down()
    return cfg, N.init(cfg, jax.random.PRNGKey(0))


def _reqs(vocab, n=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(3, vocab, 9 + 11 * i
                                               ).astype(np.int32),
                    max_new_tokens=3 + 2 * i, eos=-1) for i in range(n)]


def _serve(eng, reqs):
    """Submit everything, pump step() until every request ended, and
    note the decoding slots' positions after each decode step."""
    positions = []
    decode = eng._decode_step

    def spy(active):
        decode(active)
        positions.append([int(eng._pos[i]) for i in active])
    eng._decode_step = spy
    for r in reqs:
        eng.submit(r)
    res = []
    for _ in range(500):
        if len(res) == len(reqs):
            break
        eng.step()
        res += eng.drain_results()
    assert len(res) == len(reqs)
    eng.step()                      # an idle call: records nothing
    return {r.rid: r for r in res}, positions


@pytest.fixture(scope="module")
def served(tiny):
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, slots=3, max_len=96,
                           prefill_chunk=16, telemetry=Telemetry.on())
    res, positions = _serve(eng, _reqs(cfg.vocab))
    # the step watchdog would keep serving past a span that raised
    assert eng.last_dispatch_error is None
    return eng, res, positions


def test_working_steps_hold_their_children(served):
    eng, res, _ = served
    evs = list(eng.obs.tracer.events)
    by_seq = {e.seq: e for e in evs}
    steps = [e for e in evs if e.etype == "step"]
    assert len(steps) == len({e.tick for e in steps}) > 0
    assert all(e.parent == -1 for e in steps)
    seen = set()
    for e in evs:
        if e.etype not in PARENTS:
            continue
        seen.add(e.etype)
        p = by_seq[e.parent]
        assert p.etype in PARENTS[e.etype], (e.etype, p.etype)
        assert p.tick == e.tick
        assert p.ts <= e.ts and e.ts + e.dur <= p.ts + p.dur
        while p.parent != -1:
            p = by_seq[p.parent]
        assert p.etype == "step" and p.tick == e.tick
    assert seen == set(PARENTS)
    # the last call was idle: no step span carries its tick
    assert max(e.tick for e in steps) < eng._ticks
    # the pool spans carry the request's id
    for e in evs:
        if e.etype.startswith("pool_"):
            assert e.rid in res


def test_token_stamps_count_every_token(served):
    eng, res, _ = served
    evs = list(eng.obs.tracer.events)
    n = {rid: 0 for rid in res}
    for e in evs:
        if e.etype == "first_token":
            n[e.rid] += 1
        elif e.etype == "decode":
            assert len(e.args["rids"]) == e.args["rows"]
            for rid in e.args["rids"]:
                n[rid] += 1
    assert n == {rid: len(r.tokens) for rid, r in res.items()}


def test_decode_ctx_is_each_rows_position(served):
    eng, _, positions = served
    dec = [e for e in eng.obs.tracer.events if e.etype == "decode"]
    assert [e.args["ctx"] for e in dec] == positions


def test_a_first_step_that_compiles_records_a_compile_span(tiny):
    cfg, params = tiny
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        # a context length no other test uses: its programs compile here
        eng = ContinuousEngine(cfg, params, slots=2, max_len=88,
                               prefill_chunk=16, telemetry=Telemetry.on())
        eng.submit(_reqs(cfg.vocab, n=1)[0])
        eng.step()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    evs = list(eng.obs.tracer.events)
    by_seq = {e.seq: e for e in evs}
    step = next(e for e in evs if e.etype == "step")
    under = []
    for e in evs:
        if e.etype != "compile":
            continue
        assert e.dur > 0
        p = e
        while p.parent != -1:
            p = by_seq[p.parent]
        if p is step:
            under.append(e)
            assert step.ts <= e.ts and e.ts + e.dur <= step.ts + step.dur
    assert under


def test_off_builds_no_event_and_no_annotation(tiny, monkeypatch):
    cfg, params = tiny

    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation built with tracing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    # other tests' live tracers would still hear this process's garbage
    # collections; the off engine must not depend on them
    monkeypatch.setattr(EV, "_LIVE", ())
    eng = ContinuousEngine(cfg, params, slots=3, max_len=96,
                           prefill_chunk=16)
    res, _ = _serve(eng, _reqs(cfg.vocab, n=3, seed=1))
    assert len(res) == 3 and all(r.status == "ok" for r in res.values())
    assert len(eng.obs.tracer) == 0 and eng.obs.tracer.emitted == 0


def test_profiler_host_plane_holds_the_ring_spans(tiny, tmp_path):
    from jax.profiler import ProfileData
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, slots=3, max_len=96,
                           prefill_chunk=16, telemetry=Telemetry.on())
    _serve(eng, _reqs(cfg.vocab, n=2, seed=2))          # compiles
    n0 = eng.obs.tracer.emitted
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng, _reqs(cfg.vocab, n=3, seed=3))
    finally:
        jax.profiler.stop_trace()
    ring = [e for e in eng.obs.tracer.events
            if e.seq >= n0 and e.etype in STEP_SPANS]
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    pd = ProfileData.from_file(files[0])
    host = sorted(
        (e.start_ns, e.name) for plane in pd.planes
        if plane.name.startswith("/host:CPU") for line in plane.lines
        for e in line.events
        if e.name.removeprefix("engine.") in STEP_SPANS)
    names = [n for _, n in host]
    assert names.count("engine.step") == sum(
        e.etype == "step" for e in ring) > 0
    assert names == ["engine." + e.etype
                     for e in sorted(ring, key=lambda e: e.ts)]


def test_chrome_export_with_step_spans_validates(served):
    eng, _, _ = served
    doc = eng.obs.tracer.chrome_trace()
    assert validate_chrome_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e.get("cat") == "engine"]
    assert {e["name"] for e in spans} >= {"step", "prep", "launch", "sync",
                                          "retire"}
    assert all(e["ph"] == "X" and "parent" in e["args"] for e in spans)
    pauses = [e for e in doc["traceEvents"] if e.get("cat") == "pause"]
    assert all(e["tid"] == 2 for e in pauses)
