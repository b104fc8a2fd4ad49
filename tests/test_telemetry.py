"""Unified telemetry subsystem: metrics registry, span tracing, flight
recorder, and the legacy stats()-dict schema contract.

The invariants under test:

* the registry round-trips through both exposition formats (Prometheus
  text + JSON dict) without losing series or label values,
* every request span the engine opens is closed by the time the run
  drains — including under preemption and under a replica kill, where
  the router's fence closes the dead replica's spans and opens REPLAY
  spans that close on re-placement,
* the null sink is a true no-op (``Telemetry()`` with tracing off keeps
  the hot path allocation-free),
* the flight recorder's ring bounds memory and its fence dump is a
  self-contained, valid JSON artifact,
* the legacy ``stats()/kv_stats()/spec_stats()`` dicts — now views over
  the registry — keep their exact key sets (the schema-stability
  contract the dashboards and older tests rely on).
"""
import dataclasses
import json

import numpy as np
import pytest

from conftest import make_engine, tiny_lm
from repro.runtime.cluster import ClusterRouter
from repro.runtime.fault import FaultEvent, ReplicaFaultInjector
from repro.runtime.sampling import SamplingParams
from repro.runtime.serve import Request, ServeConfig, ServeEngine
from repro.runtime.steps import step_cache_stats
from repro.runtime.telemetry import (ENGINE_SPANS, ENGINE_TID, NULL_TRACE,
                                     ROUTER_PID, MetricsRegistry, NullTrace,
                                     Telemetry, TraceRecorder,
                                     validate_chrome_trace)


def _reqs(n=4, *, max_new=6, sampled=True, base_id=0):
    rng = np.random.default_rng(0)
    out = []
    for i in range(n):
        prompt = rng.integers(1, 60,
                              size=int(rng.integers(2, 7))).astype(np.int32)
        sp = SamplingParams(temperature=0.8 if (sampled and i % 2) else 0.0,
                            seed=5)
        out.append(Request(base_id + i, prompt, max_new_tokens=max_new,
                           sampling=sp))
    return out


# ------------------------------------------------------------- registry
def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", ("replica",))
    c.labels(replica="0").inc()
    c.labels(replica="0").inc(2)
    c.labels(replica="1").inc()
    assert reg.value("req_total", replica="0") == 3
    assert reg.value("req_total", replica="1") == 1
    g = reg.gauge("depth", "queue depth")
    g.labels().set(7)
    g.labels().dec(2)
    assert reg.value("depth") == 5
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    h.labels().observe(0.05)
    h.labels().observe(0.5)
    h.labels().observe(5.0)
    snap = h.labels().get()
    assert snap["count"] == 3 and snap["sum"] == pytest.approx(5.55)
    assert snap["buckets"] == {"0.1": 1, "1.0": 2}  # cumulative
    # re-registration is idempotent (same family), type mismatch is not
    assert reg.counter("req_total", "requests", ("replica",)) is c
    with pytest.raises(ValueError):
        reg.gauge("req_total", "requests")
    with pytest.raises(ValueError):
        c.labels(tenant="x")  # undeclared label name


def test_registry_function_backed_gauge_reads_live():
    reg = MetricsRegistry()
    state = {"v": 1}
    reg.gauge("live", "live value").labels().set_function(
        lambda: state["v"])
    assert reg.value("live") == 1
    state["v"] = 42
    assert reg.value("live") == 42
    assert reg.to_dict()["live"]["series"][0]["value"] == 42


def test_prometheus_exposition_parses(tmp_path):
    reg = MetricsRegistry()
    reg.counter("tok_total", "tokens served", ("replica",)) \
        .labels(replica="0").inc(9)
    reg.gauge("tenant_share", "escaping", ("tenant",)) \
        .labels(tenant='a"b\\c\n').set(1)
    reg.histogram("lat_s", "latency", buckets=(0.1,)).labels().observe(0.5)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE tok_total counter" in lines
    assert 'tok_total{replica="0"} 9' in lines
    # label values escape backslash, quote, newline per exposition 0.0.4
    assert any('tenant="a\\"b\\\\c\\n"' in ln for ln in lines)
    # histogram expands to _bucket (cumulative, +Inf last) + _sum + _count
    assert 'lat_s_bucket{le="0.1"} 0' in lines
    assert 'lat_s_bucket{le="+Inf"} 1' in lines
    assert "lat_s_count 1" in lines
    # write() routes on extension: .prom = text, else JSON
    prom = tmp_path / "m.prom"
    reg.write(str(prom))
    assert prom.read_text() == text
    js = tmp_path / "m.json"
    reg.write(str(js))
    assert json.loads(js.read_text())["tok_total"]["type"] == "counter"


# ---------------------------------------------------------------- traces
def test_trace_roundtrip_and_validation(tmp_path):
    tr = TraceRecorder()
    tr.set_process_name(0, "replica 0")
    tr.begin(0, 1, "PREFILL", slot=0)
    tr.instant(0, "hb_miss", tid=1)
    tr.counter(0, "engine", {"live_slots": 1})
    tr.end(0, 1, tokens=3)
    path = tmp_path / "t.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert phases.count("B") == phases.count("E") == 1
    v = validate_chrome_trace(str(path))
    assert v["balanced"] and not v["unbalanced"] and v["pids"] == [0]
    # an unclosed span is flagged, not silently dropped
    tr.begin(0, 2, "DECODE")
    v2 = validate_chrome_trace(tr.to_chrome())
    assert not v2["balanced"] and v2["unbalanced"]
    assert tr.open_spans() == {(0, 2): ["DECODE"]}
    assert tr.end_if_open(0, 2) and not tr.end_if_open(0, 2)


def test_validator_rejects_malformed(tmp_path):
    with pytest.raises(ValueError):
        validate_chrome_trace({"no_events_here": 1})
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "B"}]}))
    with pytest.raises(ValueError):
        validate_chrome_trace(str(bad))


def test_ring_buffer_bounds_memory():
    tr = TraceRecorder(limit=16)
    for i in range(100):
        tr.instant(0, f"e{i}")
    assert len(tr.events) == 16
    assert tr.total == 100 and tr.dropped == 84
    assert [e["name"] for e in tr.tail(2)] == ["e98", "e99"]


def test_null_sink_is_noop():
    nt = NullTrace()
    assert not nt.enabled and not NULL_TRACE.enabled
    nt.begin(0, 1, "X")
    nt.end(0, 1)
    nt.instant(0, "y")
    nt.counter(0, "c", {})
    assert nt.end_all(0) == 0 and not nt.end_if_open(0, 1)
    # default Telemetry routes to the shared null sink; metrics still work
    tm = Telemetry()
    assert tm.trace is NULL_TRACE
    tm.req_transition(0, 1, "QUEUED")
    tm.req_end(0, 1)
    assert tm.dump_flight("nothing-armed") is None
    with pytest.raises(ValueError):
        tm.write_trace("nowhere.json")


# ------------------------------------------------- engine instrumentation
def test_engine_spans_balanced_and_metrics(tmp_path):
    tm = Telemetry(trace=True)
    model, params = tiny_lm()
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, max_len=64), telemetry=tm)
    for r in _reqs(4):
        eng.submit(r)
    done = eng.run()
    assert len(done) == 4
    assert tm.trace.open_spans() == {}
    names = {e["name"] for e in tm.trace.events if e["ph"] == "B"}
    assert {"QUEUED", "PREFILL", "DECODE"} <= names
    reg = tm.registry
    assert reg.value("engine_requests_submitted_total", replica="0") == 4
    fam = reg.to_dict()["engine_requests_finished_total"]
    assert sum(s["value"] for s in fam["series"]
               if s["labels"]["replica"] == "0") == 4
    assert reg.value("engine_tokens_total", replica="0") == \
        sum(len(r.output) for r in done)
    assert reg.value("engine_ticks_total", replica="0") > 0
    assert reg.value("engine_live_slots", replica="0") == 0
    path = tm.write_trace(str(tmp_path / "engine.json"))
    assert validate_chrome_trace(path)["balanced"]


@pytest.mark.parametrize("mode, cache", [("continuous", "paged"),
                                         ("wave", "dense")])
def test_engine_tick_phases_on_the_engine_row(tmp_path, mode, cache):
    """``Telemetry.span`` records each tick phase as a B/E pair on the
    engine row, with its stats, and the trace stays balanced."""
    tm = Telemetry(trace=True)
    model, params = tiny_lm()
    eng = ServeEngine(model, params, ServeConfig(
        batch_slots=2, max_len=64, mode=mode, cache=cache, page_size=8),
        telemetry=tm)
    for r in _reqs(4, sampled=False):
        eng.submit(r)
    done = eng.run()
    row = [e for e in tm.trace.events if e["tid"] == ENGINE_TID]
    begins = [e for e in row if e["ph"] == "B"]
    assert len(begins) == sum(e["ph"] == "E" for e in row)
    want = set(ENGINE_SPANS) - ({"engine.prefill"} if mode == "wave"
                                else set())
    assert {e["name"] for e in begins} == want
    assert sum(e["name"] == "engine.step" for e in begins) == \
        tm.registry.value("engine_ticks_total", replica="0")
    ends = [e.get("args", {}) for e in row if e["ph"] == "E"]
    emitted = sum(a.get("emitted", 0) for a in ends)
    first = len(done) if mode == "continuous" else 0  # from prefill
    assert emitted + first == sum(len(r.output) for r in done)
    assert sum(a.get("admitted", 0) for a in ends) == len(done)
    if mode == "continuous":
        prefills = [e["args"] for e in begins if e["name"] == "engine.prefill"]
        assert sorted(a["tokens"] for a in prefills) == \
            sorted(len(r.prompt) for r in done)
    path = tm.write_trace(str(tmp_path / "phases.json"))
    assert validate_chrome_trace(path)["balanced"]


def test_span_without_a_recorder_is_an_annotation_only():
    tm = Telemetry()
    with tm.span("engine.step", pid=3) as sp:
        sp.set(emitted=1)
    assert tm.trace is NULL_TRACE and tm.trace.open_spans() == {}


def test_preemption_spans_balanced():
    tm = Telemetry(trace=True)
    model, params = tiny_lm()
    eng = ServeEngine(model, params, ServeConfig(
        batch_slots=2, max_len=64, policy="drf-fair", preempt=True,
        tenant_weights={"gold": 3, "free": 1},
        victim_policy="lowest-weight-share-first"), telemetry=tm)
    gold = [dataclasses.replace(r, tenant="gold")
            for r in _reqs(4, max_new=10, sampled=False)]
    for r in gold:
        eng.submit(r)
    eng.step()
    eng.step()
    free = [dataclasses.replace(r, tenant="free")
            for r in _reqs(2, max_new=4, sampled=False, base_id=50)]
    for r in free:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 6
    assert eng.scheduler.preempted_total >= 1
    names = [e["name"] for e in tm.trace.events if e["ph"] == "B"]
    assert "PREEMPTED" in names
    assert tm.trace.open_spans() == {}
    assert tm.registry.value("serve_preempted", replica="0") >= 1


def test_cluster_chaos_spans_and_flight_dump(tmp_path):
    tm = Telemetry(trace=True, flight=128, flight_dir=str(tmp_path))
    model, params = tiny_lm()

    def make(rid):
        return ServeEngine(model, params,
                           ServeConfig(batch_slots=2, max_len=64))

    injector = ReplicaFaultInjector([FaultEvent(4, "kill", 1),
                                     FaultEvent(24, "rejoin", 1)])
    router = ClusterRouter(make, 3, policy="spread", miss_threshold=2,
                           injector=injector, telemetry=tm)
    for r in _reqs(9, max_new=8):
        router.submit(r)
    done = router.run(max_ticks=4000)
    assert len(done) == 9
    assert all(r.finish_reason != "failed" for r in done)
    assert tm.trace.open_spans() == {}
    replays = [e for e in tm.trace.events
               if e["ph"] == "B" and e["name"] == "REPLAY"]
    assert replays and all(e["pid"] == ROUTER_PID for e in replays)
    instants = {e["name"] for e in tm.trace.events if e["ph"] == "i"}
    assert {"hb_miss", "replica_lost", "place"} <= instants
    # the fence armed the flight recorder: one dump, self-contained
    assert len(tm.flight_dumps) == 1
    dump = json.loads(open(tm.flight_dumps[0]).read())
    assert dump["reason"].startswith("fence-replica1")
    assert dump["recovered"] >= 1
    # the dump is a fence-time snapshot: the victims' REPLAY spans are
    # open in it (they close later, on re-placement)
    assert any("REPLAY" in names for names in dump["open_spans"].values())
    assert dump["events"] and "cluster_recoveries" in dump["metrics"]
    assert tm.registry.value("cluster_recoveries") >= 1


# -------------------------------------------------- schema stability
def test_stats_schemas_are_registry_views():
    """The legacy dicts are now registry reads — their key sets are a
    frozen contract (dashboards + older tests parse them)."""
    eng = make_engine(batch_slots=2, max_len=64, cache="paged",
                      page_size=8, draft_k=2)
    for r in _reqs(3, sampled=False):
        eng.submit(r)
    eng.run()
    assert set(eng.kv_stats()) == {
        "cache", "kv_reserved_bytes", "page_size", "capacity_pages",
        "in_use_pages", "prefix_entries", "prefix_hits", "prefix_misses"}
    assert set(eng.spec_stats()) == {
        "draft_k", "drafter", "proposed", "accepted", "acceptance_rate",
        "spec_ticks", "tokens_per_tick"}
    assert set(eng.offer()) == {"free_slots", "free_pages", "page_size",
                                "queue_depth"}
    assert set(step_cache_stats()) == {"size", "hits", "misses", "build_s"}

    model, params = tiny_lm()
    router = ClusterRouter(
        lambda rid: ServeEngine(model, params,
                                ServeConfig(batch_slots=2, max_len=64)), 2)
    for r in _reqs(2, sampled=False):
        router.submit(r)
    router.run(max_ticks=2000)
    st = router.stats()
    assert set(st) == {"ticks", "recoveries", "replicas_lost", "failed",
                       "brownout_ticks", "queued", "replicas"}
    assert set(st["replicas"][0]) == {"state", "placements", "steps",
                                      "slow", "flags"}
