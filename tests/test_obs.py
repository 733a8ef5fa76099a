"""Unified telemetry subsystem (repro.obs): metrics registry semantics,
lifecycle-tracer ring + Chrome-trace export, engine event-order
invariants (admit before first token, resume only after preempt, finish
exactly once), registry-backed attribute shims, pool-metric mirroring,
dispatch-profiler coverage of all four hot dispatches, and the
scripts/trace_report.py CLI exit codes."""

import dataclasses
import importlib.util
import json
import os
import time

import numpy as np
import jax
import pytest

from repro import configs as CONFIGS
from repro.models import network as N
from repro.obs import Telemetry
from repro.obs.events import Tracer, validate_chrome_trace
from repro.obs.metrics import (NULL_METRIC, Counter, Histogram,
                               MetricsRegistry)
from repro.obs.profile import DISPATCH_NAMES
from repro.serving.engine import ContinuousEngine, Request

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = CONFIGS.get("qwen2_0_5b").scaled_down()
    params = N.init(cfg, KEY)
    return cfg, params


def _shared_prefix_reqs(vocab, n=4, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, vocab, 32).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(3, vocab, 5 + i
                                              ).astype(np.int32)]),
                    max_new_tokens=4, eos=-1) for i in range(n)]


@pytest.fixture(scope="module")
def traced(tiny):
    """One fully-instrumented run (tracer + profiler + ngram spec over a
    shared-prefix trace) shared by the integration tests below."""
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, slots=2, max_len=96,
                           spec="ngram", spec_k=4,
                           telemetry=Telemetry.on(profile=True))
    res = eng.run(_shared_prefix_reqs(cfg.vocab))
    return cfg, params, eng, res


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_basics():
    m = MetricsRegistry()
    c = m.counter("a.count", "help")
    c.inc()
    c.inc(2.5)
    g = m.gauge("a.gauge")
    g.set(7)
    g.inc(-2)
    h = m.histogram("a.hist", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    s = m.series("a.series")
    s.append(1.0)
    s.append(2.0)
    assert m.value("a.count") == 3.5
    assert m.value("a.gauge") == 5
    assert h.count == 4 and h.sum == 555.5
    assert len(s) == 2 and s.total == 2   # total = lifetime appends
    assert m.counter("a.count").value == 3.5      # same object back
    assert m.get("nope") is None and m.value("nope") == 0.0


def test_registry_kind_mismatch_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_disabled_registry_records_nothing():
    m = MetricsRegistry(enabled=False)
    c = m.counter("x")
    assert c is NULL_METRIC
    c.inc(5)
    m.histogram("h").observe(3)
    m.series("s").append(1)
    assert len(m) == 0
    assert m.snapshot() == {}
    assert m.value("x") == 0.0


def test_snapshot_json_round_trip():
    m = MetricsRegistry()
    m.counter("c").inc(3)
    m.gauge("g").set(1.5)
    h = m.histogram("h")
    for v in range(1, 101):
        h.observe(v)
    m.series("s").append(9)
    snap = json.loads(m.to_json())
    assert snap == m.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 100
    assert 45 <= snap["histograms"]["h"]["p50"] <= 55
    assert snap["series"]["s"]["total"] == 1
    assert snap["series"]["s"]["last"] == 9


def test_prometheus_exposition_well_formed():
    m = MetricsRegistry()
    m.counter("engine.steps", "decode steps").inc(4)
    m.gauge("pool util").set(0.5)             # name needs sanitizing
    m.histogram("lat", buckets=(1, 2)).observe(1.5)
    m.series("stamps").append(1.0)
    text = m.to_prometheus()
    lines = [ln for ln in text.splitlines() if ln]
    names = set()
    for ln in lines:
        if ln.startswith("# HELP") or ln.startswith("# TYPE"):
            continue
        name, val = ln.rsplit(" ", 1)
        float(val)                             # every sample is numeric
        names.add(name.split("{")[0])
    assert "engine_steps" in names and "pool_util" in names
    assert 'lat_bucket{le="1"}' in text and 'lat_bucket{le="+Inf"}' in text
    assert "lat_sum" in names and "lat_count" in names
    assert "stamps_total" in names             # series export as counters
    # bucket counts are cumulative and end at the total count
    buckets = [int(ln.rsplit(" ", 1)[1]) for ln in lines
               if ln.startswith("lat_bucket")]
    assert buckets == sorted(buckets) and buckets[-1] == 1


def test_histogram_percentiles_exact_over_reservoir():
    h = Histogram("h")
    for v in range(1, 11):
        h.observe(v)
    assert h.percentile(0) == 1
    assert h.percentile(100) == 10
    assert 5 <= h.percentile(50) <= 6


# ---------------------------------------------------------------------------
# tracer ring + chrome export
# ---------------------------------------------------------------------------

def test_tracer_ring_bounds_and_counts_drops():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.event("decode", step=i, dur=1e-6)
    assert len(tr) == 8
    assert tr.emitted == 20 and tr.dropped == 12
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 12
    assert validate_chrome_trace(doc) == []


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    tr.event("submit", rid=1)
    tr.counter("x", 1.0)
    assert len(tr) == 0 and tr.emitted == 0


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) == ["top level is not a JSON object"]
    assert validate_chrome_trace({}) == ["missing traceEvents array"]
    bad = {"traceEvents": [{"ph": "X", "name": "n", "pid": 1, "tid": 0,
                            "ts": 1.0}]}          # X without dur
    assert any("dur" in e for e in validate_chrome_trace(bad))
    bad2 = {"traceEvents": [{"ph": "i", "pid": 1, "tid": "zero",
                             "ts": 0.0, "name": "n"}]}
    assert any("tid" in e for e in validate_chrome_trace(bad2))


# ---------------------------------------------------------------------------
# engine lifecycle invariants
# ---------------------------------------------------------------------------

def _events_by_rid(tracer):
    out = {}
    for e in tracer.events:
        if e.rid >= 0:
            out.setdefault(e.rid, []).append(e)
    return out


def test_lifecycle_event_order_invariants(traced):
    """Per request: one submit, admit after submit, first_token at or
    after admit, exactly one finish last."""
    cfg, params, eng, res = traced
    by_rid = _events_by_rid(eng.obs.tracer)
    assert set(by_rid) == {r.rid for r in res}
    for rid, evs in by_rid.items():
        kinds = [e.etype for e in evs]
        assert kinds.count("submit") == 1
        assert kinds.count("admit") == 1
        assert kinds.count("finish") == 1
        t = {e.etype: e.ts for e in evs}
        assert t["submit"] <= t["admit"] <= t["first_token"] < t["finish"]
        assert kinds[-1] == "finish"
        # ttft mark happens once, before any decode emission completes
        assert kinds.count("first_token") == 1
    # engine-level spans and counter samples exist alongside
    etypes = {e.etype for e in eng.obs.tracer.events}
    assert "chunk_batch" in etypes
    assert {"verify", "decode"} & etypes
    assert any(name == "pool_util" for name, *_ in eng.obs.tracer.counters)


def test_telemetry_off_engine_traces_nothing(tiny):
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, slots=2, max_len=96)
    res = eng.run(_shared_prefix_reqs(cfg.vocab, n=2, seed=3))
    assert len(eng.obs.tracer) == 0            # ring off by default
    assert eng.obs.profiler is None
    assert eng.steps > 0                       # ...but metrics still count
    assert eng.metrics.value("engine.requests_finished") == len(res)


def test_preempt_resume_event_order(tiny):
    """Resume events only ever follow a preempt for the same rid, and the
    preemption counter agrees with the event stream."""
    cfg, params = tiny
    rng = np.random.default_rng(31)
    reqs = [Request(rid=0, prompt=rng.integers(3, cfg.vocab, 60
                                               ).astype(np.int32),
                    max_new_tokens=24, eos=-1),
            Request(rid=1, prompt=rng.integers(3, cfg.vocab, 60
                                               ).astype(np.int32),
                    max_new_tokens=24, eos=-1),
            Request(rid=2, prompt=rng.integers(3, cfg.vocab, 100
                                               ).astype(np.int32),
                    max_new_tokens=12, eos=-1)]
    for i in range(3, 7):
        reqs.append(Request(rid=i, prompt=rng.integers(3, cfg.vocab, 6
                                                       ).astype(np.int32),
                            max_new_tokens=3, eos=-1, ttft_slo=1e-4))
    eng = ContinuousEngine(cfg, params, slots=4, max_len=160,
                           kv_blocks=20, policy="slo_preempt", audit=True,
                           telemetry=Telemetry.on())
    # the three long requests take the whole pool (19 usable blocks) and
    # decode before the deadline-carrying ones arrive, and those have
    # waited past their risk point at the next step however fast the host
    # is: serving them takes preemptions
    for r in reqs[:3]:
        eng.submit(dataclasses.replace(r))
    for _ in range(20):
        if all(s is not None and s.phase == "decode"
               for s in eng._slots[:3]):
            break
        eng.step()
    for r in reqs[3:]:
        eng.submit(dataclasses.replace(r))
    time.sleep(1e-3)
    done = []
    for _ in range(500):
        if len(done) == len(reqs):
            break
        eng.step()
        done += eng.drain_results()
    assert len(done) == len(reqs)
    assert eng.preemptions > 0                 # overload really preempted
    n_preempt = 0
    for rid, evs in _events_by_rid(eng.obs.tracer).items():
        kinds = [e.etype for e in evs]
        n_preempt += kinds.count("preempt")
        assert kinds.count("finish") == 1 and kinds[-1] == "finish"
        for i, k in enumerate(kinds):
            if k == "resume":
                assert "preempt" in kinds[:i], (rid, kinds)
    assert n_preempt == eng.preemptions
    assert n_preempt == eng.metrics.value("engine.preemptions")


# ---------------------------------------------------------------------------
# registry-backed attribute shims + pool mirroring
# ---------------------------------------------------------------------------

def test_property_shims_read_registry(traced):
    cfg, params, eng, res = traced
    m = eng.metrics
    assert eng.steps == int(m.value("engine.steps")) > 0
    assert eng.chunk_steps == int(m.value("engine.chunk_steps")) > 0
    assert eng.prefills == int(m.value("engine.prefills")) == len(res)
    assert eng.preemptions == int(m.value("engine.preemptions"))
    assert len(eng.decode_times) == eng.steps
    assert m.value("engine.tokens_emitted") == sum(
        len(r.tokens) for r in res)
    assert m.get("engine.ttft_steps").count == len(res)


def test_pool_metrics_mirror_plain_ints(traced):
    cfg, params, eng, res = traced
    pool, m = eng.pool, eng.metrics
    assert pool.shared_token_hits > 0          # shared-prefix trace
    assert m.value("kv_pool.shared_token_hits") == pool.shared_token_hits
    assert m.value("kv_pool.cow_forks") == pool.cow_forks
    assert m.value("kv_pool.evictions") == pool.evictions
    assert m.value("kv_pool.peak_used_blocks") == pool.peak_used


def test_spec_draft_counter_shim(tiny):
    from repro.serving.spec import ModelDraft
    cfg, params = tiny
    md = ModelDraft(cfg, params)
    assert isinstance(md._c_steps, Counter)
    md._c_steps.inc(3)
    assert md.steps == 3                       # property reads the counter
    assert md.chunk_steps == 0


def test_schedule_metrics_bound_to_engine_registry(traced):
    cfg, params, eng, res = traced
    assert eng.metrics.value("schedule.hits") == eng.schedule.stats()["hits"]


# ---------------------------------------------------------------------------
# dispatch profiler
# ---------------------------------------------------------------------------

def test_profiler_covers_all_four_dispatches(traced):
    cfg, params, eng, res = traced
    prof = eng.obs.profiler
    names = {s["name"] for s in prof.spans}
    assert names == set(DISPATCH_NAMES)
    for s in prof.spans:
        assert s["dur_s"] > 0
        assert s["modeled_cycles"] > 0
        assert s["modeled_traffic"] > 0
        assert s["kind"] in ("serve", "calibration")
    # calibration guarantees coverage even for fused/absent dispatches
    cal = {s["name"] for s in prof.spans if s["kind"] == "calibration"}
    assert cal == set(DISPATCH_NAMES)
    # every dispatch got a latency histogram in the registry
    for name in DISPATCH_NAMES:
        h = eng.metrics.get(f"profile.{name}_us")
        assert h is not None and h.count > 0
    # jaxpr-walk costs attached where the lint pass traces them
    assert prof.model["decode_step"]["flops"] > 0
    assert prof.model["decode_step"]["bytes"] > 0


def test_profiler_requires_paged_engine(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(cfg, params, slots=2, max_len=96, paged=False,
                         telemetry=Telemetry.on(profile=True))


# ---------------------------------------------------------------------------
# exporters + trace_report CLI
# ---------------------------------------------------------------------------

def _load_trace_report():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_export_and_trace_report_cli(traced, tmp_path):
    cfg, params, eng, res = traced
    trace = str(tmp_path / "trace.json")
    metrics = str(tmp_path / "metrics.json")
    prom = str(tmp_path / "metrics.prom")
    eng.obs.export_trace(trace)
    eng.obs.export_metrics(metrics)
    eng.obs.metrics.export(prom)

    with open(trace) as f:
        assert validate_chrome_trace(json.load(f)) == []
    with open(metrics) as f:
        assert json.load(f)["counters"]["engine.steps"] == eng.steps
    with open(prom) as f:
        assert "# TYPE engine_steps counter" in f.read()

    tr = _load_trace_report()
    assert tr.main([trace, "--metrics", metrics, "--validate"]) == 0
    # missing expected dispatch -> nonzero under --validate
    assert tr.main([trace, "--validate",
                    "--expect-dispatches", "decode_step,nope"]) == 1
    # malformed input -> nonzero
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert tr.main([str(bad)]) == 1
    empty = tmp_path / "empty.json"
    empty.write_text('{"traceEvents": []}')
    assert tr.main([str(empty), "--validate"]) == 1


def test_tracer_export_matches_live_trace(traced, tmp_path):
    cfg, params, eng, res = traced
    doc = eng.obs.tracer.chrome_trace()
    disp = [e for e in doc["traceEvents"] if e.get("cat") == "dispatch"]
    assert disp                                 # profiled spans in trace
    assert {e["args"]["dispatch"] for e in disp} <= set(DISPATCH_NAMES)
    assert all(e["pid"] == 2 for e in disp)     # profiler track
    slots_tids = {e["tid"] for e in doc["traceEvents"]
                  if e.get("pid") == 1 and e["tid"] >= 100}
    assert slots_tids <= {100, 101}             # one track per slot
