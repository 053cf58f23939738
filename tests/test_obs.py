"""StreamTrace observability: recorder, span assembly, exporters, flight
recorder, and the trace-off zero-cost contract.

Layers covered:

* ``TraceRecorder`` ring semantics (per-worker capacity, global seq merge,
  overflow accounting) and the ``NullRecorder`` no-op default
* ``compute_phases`` — the queued/prefill/decode/stall attribution and its
  exact sum-to-latency identity
* nearest-rank percentiles in ``PerformanceMonitor.summary()`` (the
  off-by-one fix)
* end-to-end ``trace="on"`` runs: lifecycle events at every edge, phase
  identity on every RequestRecord, valid Chrome-trace JSON with spans per
  lane per worker, Prometheus exposition with the latency histograms
* trace determinism: two seeded runs produce bit-identical event streams
* FlowGuard staleness: stale workers are skipped and surfaced as
  ``metrics_stale`` events (the silent-fresh regression)
* flight recorder: non-empty dumps on ``fail_worker`` and on an engine
  exception; the traceview CLI renders them
"""
import json

import pytest

from repro.core.metrics import PerformanceMonitor, RequestRecord
from repro.obs.spans import compute_phases
from repro.obs.trace import (
    EV_ADMIT,
    EV_COUNTERS,
    EV_DECODE_STEP,
    EV_ENQUEUE,
    EV_FINISH,
    EV_KV_ALLOC,
    EV_METRICS_STALE,
    EV_PREFILL_CHUNK,
    EV_PREFILL_END,
    EV_PREFILL_PREEMPT,
    EV_PREFILL_RESUME,
    EV_PREFILL_START,
    EV_ROUTE,
    EV_SUBMIT,
    EV_VERIFY,
    EV_WORKER_FAIL,
    EVENT_NAMES,
    EVENT_SCHEMAS,
    NullRecorder,
    TraceRecorder,
    make_recorder,
)


# ------------------------------------------------------------------ recorder
def test_event_names_and_schemas_aligned():
    assert len(EVENT_NAMES) == len(set(EVENT_NAMES))
    assert set(EVENT_SCHEMAS) == set(EVENT_NAMES)


def test_null_recorder_is_noop():
    r = NullRecorder()
    assert not r.enabled
    r.emit(1.0, 0, EV_SUBMIT, "req-x", (1, 2, 3))
    assert r.events() == []
    assert r.to_dump("x", 5.0)["events"] == []


def test_make_recorder_modes():
    assert isinstance(make_recorder("off"), NullRecorder)
    assert isinstance(make_recorder("on"), TraceRecorder)
    assert isinstance(make_recorder("flight", capacity=7), TraceRecorder)
    with pytest.raises(ValueError):
        make_recorder("sometimes")
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_ring_merge_and_overflow():
    r = TraceRecorder(capacity=4)
    for i in range(6):  # worker 0: 6 events through a 4-slot ring
        r.emit(float(i), 0, EV_SUBMIT, f"req-{i}", (i,))
    r.emit(99.0, 1, EV_ENQUEUE, "req-b", (1,))
    evs = r.events()
    # worker 0 keeps its LAST 4 events; worker 1 is unaffected
    assert [e[4] for e in evs if e[2] == 0] == ["req-2", "req-3", "req-4", "req-5"]
    assert r.dropped == 2
    # global seq gives a total order across workers
    assert [e[0] for e in evs] == sorted(e[0] for e in evs)
    dump = r.to_dump("test", 99.0)
    assert dump["reason"] == "test" and dump["dropped"] == 2
    assert all(row[3] in EVENT_NAMES for row in dump["events"])
    json.dumps(dump)  # JSON-serializable
    r.clear()
    assert r.events() == [] and r.dropped == 0


# ------------------------------------------------------------- span assembly
@pytest.mark.parametrize(
    "t0,ps,pe,ft,te,active",
    [
        (0.0, 2.0, 3.0, 3.0, 10.0, 0),    # one-shot admit
        (0.0, 1.0, 5.0, 5.0, 12.0, 4),    # chunked, fully active
        (0.0, 1.0, 8.0, 8.0, 15.0, 3),    # chunked with preemption stalls
        (2.0, 2.0, 0.0, 0.0, 6.0, 0),     # died mid-prefill (no end stamps)
        (0.0, 0.0, 0.0, 0.0, 4.0, 0),     # never prefilled (queued kill)
        (1.0, 1.0, 1.0, 1.0, 1.0, 0),     # zero-latency degenerate
    ],
)
def test_compute_phases_identity(t0, ps, pe, ft, te, active):
    queued, prefill, decode, stall = compute_phases(t0, ps, pe, ft, te, active)
    assert queued >= 0 and prefill >= 0 and decode >= 0 and stall >= 0
    assert queued + prefill + decode + stall == pytest.approx(te - t0)


def test_compute_phases_attribution():
    # submitted t=0, prefill starts t=2 (queued 2), chunked across 2 active
    # ticks ending t=6 (prefill window 4, only 1 tick of service past the
    # start tick -> stall picks up the parked ticks), decode 6 -> 10
    queued, prefill, decode, stall = compute_phases(0.0, 2.0, 6.0, 6.0, 10.0, 2)
    assert queued == 2.0
    assert decode == 4.0
    assert prefill == 1.0  # active - 1: first granted turn lands on the start tick
    assert stall == 3.0


# ------------------------------------------------- nearest-rank percentiles
def _mon_with_latencies(lats):
    mon = PerformanceMonitor(1)
    for i, lat in enumerate(lats):
        mon.complete_request(RequestRecord(
            request_id=f"r{i}", t_start=0.0, t_end=lat, generated=1,
            token_times=[lat],
        ))
    return mon


def test_percentile_nearest_rank():
    s = _mon_with_latencies([1.0, 2.0, 3.0, 4.0])
    # nearest-rank: p50 of 4 samples is the 2nd value, not the 3rd
    assert s.summary()["latency_p50"] == 2.0
    assert s.summary()["latency_p99"] == 4.0
    s = _mon_with_latencies([5.0])
    assert s.summary()["latency_p50"] == 5.0
    assert s.summary()["latency_p99"] == 5.0
    s = _mon_with_latencies(list(map(float, range(1, 101))))
    assert s.summary()["latency_p50"] == 50.0
    assert s.summary()["latency_p90"] == 90.0
    assert s.summary()["latency_p99"] == 99.0


# ------------------------------------------------------------- end to end
def _etypes(events):
    return {e[3] for e in events}


def serve_all(engine, reqs, max_steps=600):
    for r in reqs:
        engine.submit(r)
    for _ in range(max_steps):
        if engine.drained():
            break
        engine.step()
    assert engine.drained()


def test_trace_off_is_default_and_empty(engine_factory, trace_factory):
    engine = engine_factory()
    assert isinstance(engine.trace, NullRecorder)
    serve_all(engine, trace_factory("bursty", n=2))
    assert engine.trace_events() == []
    assert engine.flight_dumps == []


def test_trace_on_lifecycle_events(engine_factory, trace_factory):
    engine = engine_factory(n_pairs=2, trace="on")
    reqs = trace_factory("mixed_slo", n=6)
    serve_all(engine, reqs)
    evs = engine.trace_events()
    got = _etypes(evs)
    for ev in (EV_SUBMIT, EV_ROUTE, EV_ENQUEUE, EV_PREFILL_START,
               EV_PREFILL_END, EV_ADMIT, EV_DECODE_STEP, EV_VERIFY,
               EV_KV_ALLOC, EV_FINISH, EV_COUNTERS):
        assert ev in got, f"missing {EVENT_NAMES[ev]} events"
    # control-plane events live on worker -1; every request has a full span
    assert all(e[2] == -1 for e in evs if e[3] in (EV_SUBMIT, EV_ROUTE))
    for r in reqs:
        kinds = _etypes(engine.trace.events_for(r.request_id))
        assert {EV_SUBMIT, EV_ROUTE, EV_PREFILL_START, EV_ADMIT,
                EV_FINISH} <= kinds
    # the route payload carries the FlowGuard per-worker score breakdown
    route = next(e for e in evs if e[3] == EV_ROUTE)
    worker, breakdown = route[5]
    assert worker in (0, 1)
    assert breakdown and all(len(terms) == 7 for terms in breakdown)
    # monotone global seq; ticks never decrease along it
    seqs = [e[0] for e in evs]
    assert seqs == sorted(seqs)


def test_trace_phase_identity_and_summary(engine_factory, trace_factory):
    engine = engine_factory(n_pairs=2, trace="on")
    serve_all(engine, trace_factory("uniform", n=5))
    recs = engine.monitor.completed
    assert recs
    for r in recs:
        total = r.phase_queued + r.phase_prefill + r.phase_decode + r.phase_stall
        assert total == pytest.approx(r.latency), r.request_id
        assert set(r.phases) == {"queued", "prefill", "decode", "stall"}
    s = engine.monitor.summary()
    for k in ("phase_queued_mean", "phase_prefill_mean",
              "phase_decode_mean", "phase_stall_mean"):
        assert k in s and s[k] >= 0.0
    phase_sum = (s["phase_queued_mean"] + s["phase_prefill_mean"]
                 + s["phase_decode_mean"] + s["phase_stall_mean"])
    assert phase_sum == pytest.approx(s["latency_mean"])
    # finish payloads carry the same breakdown the records hold
    fin = {e[4]: e[5] for e in engine.trace_events() if e[3] == EV_FINISH}
    for r in recs:
        gen, _evicted, q, p, d, st = fin[r.request_id]
        assert (q, p, d, st) == (r.phase_queued, r.phase_prefill,
                                 r.phase_decode, r.phase_stall)
        assert gen == r.generated


def test_chrome_trace_and_prometheus(engine_factory, trace_factory, tmp_path):
    engine = engine_factory(n_pairs=2, trace="on")
    serve_all(engine, trace_factory("bursty", n=8))
    path = tmp_path / "trace.json"
    engine.export_chrome_trace(str(path))
    doc = json.load(open(path))  # valid, loadable JSON
    assert doc["traceEvents"]
    # >= 1 span per lane per worker that served traffic
    workers = {e[2] for e in engine.trace_events()
               if e[3] == EV_DECODE_STEP and e[2] >= 0}
    assert workers  # at least one pair decoded
    spans = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            spans.setdefault(ev["pid"], set()).add(ev["tid"])
    for w in sorted(workers):
        assert spans.get(w) == {0, 1, 2}, f"pair{w} missing a lane span"
    counters = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "C"}
    assert {"queue_depth", "kv_free_pages", "acceptance_ema",
            "mean_depth"} <= counters
    txt = engine.prometheus_text()
    assert "# TYPE streamserve_ttft_ticks histogram" in txt
    assert "# TYPE streamserve_tpot_ticks histogram" in txt
    assert "streamserve_ttft_ticks_bucket" in txt
    assert "streamserve_requests_total" in txt
    for phase in ("queued", "prefill", "decode", "stall"):
        assert f"streamserve_phase_{phase}_ticks_bucket" in txt
    # rendering is deterministic (registration order + sorted labels)
    assert txt == engine.prometheus_text()


# ------------------------------------------------------------- determinism
def _normalized_events(engine, reqs):
    """Event stream with request ids rewritten by submission index (the
    process-global req-N counter differs between runs)."""
    order = {r.request_id: f"req#{i}" for i, r in enumerate(reqs)}
    return [
        (seq, tick, worker, etype, order.get(rid, rid),
         tuple(order.get(x, x) if isinstance(x, str) else x for x in payload))
        for seq, tick, worker, etype, rid, payload in engine.trace_events()
    ]


def test_trace_streams_are_deterministic(engine_factory, trace_factory):
    streams = []
    for _ in range(2):
        engine = engine_factory(n_pairs=2, trace="on")
        reqs = trace_factory("mixed_slo", n=6, seed=3)
        serve_all(engine, reqs)
        streams.append(_normalized_events(engine, reqs))
    assert streams[0] == streams[1]


# --------------------------------------------------------------- staleness
def test_stale_worker_skipped_and_traced(engine_factory, trace_factory):
    """A worker that stops reporting must stop attracting traffic — the
    scheduler's derived queue-depth refresh must not mask staleness."""
    engine = engine_factory(n_pairs=2, trace="on")
    reqs = trace_factory("bursty", n=8, seed=5)
    # worker 1 last reported far in the past; worker 0 is fresh NOW
    engine._now = 100.0
    engine.monitor.update_worker(0)
    engine.monitor.workers[1].timestamp = 1.0
    for r in reqs:
        engine.submit(r)
    assert all(w == 0 for _, w in engine.scheduler.routing_log), \
        "stale worker won traffic"
    stale = [e for e in engine.trace_events() if e[3] == EV_METRICS_STALE]
    assert stale and all(e[2] == 1 for e in stale)
    assert all(e[5][0] > 0 for e in stale)  # positive age payload


def test_derived_refresh_does_not_touch_timestamp():
    mon = PerformanceMonitor(1, clock=lambda: 50.0)
    mon.workers[0].timestamp = 1.0
    mon.update_worker(0, queue_depth=3, touch=False)
    assert mon.workers[0].timestamp == 1.0 and mon.workers[0].queue_depth == 3
    mon.update_worker(0, queue_depth=4)
    assert mon.workers[0].timestamp == 50.0


# --------------------------------------------------------- chunked prefill
def test_chunked_preempt_resume_events(engine_factory, tiny_model):
    import numpy as np

    from repro.serving.request import Request, SamplingParams

    cfg, _ = tiny_model
    engine = engine_factory(trace="on", prefill_chunk=16, max_batch=3)
    rng = np.random.default_rng(7)
    long = Request(prompt=rng.integers(0, cfg.vocab_size, 80).tolist(),
                   params=SamplingParams(max_new_tokens=4))
    engine.submit(long)
    engine.step()  # long starts chunking
    tight = Request(prompt=rng.integers(0, cfg.vocab_size, 20).tolist(),
                    params=SamplingParams(max_new_tokens=4), slo_ttft=3.0)
    engine.submit(tight)  # earlier deadline: parks the long at the boundary
    engine.run_until_done()
    got = _etypes(engine.trace_events())
    assert EV_PREFILL_CHUNK in got
    assert EV_PREFILL_PREEMPT in got and EV_PREFILL_RESUME in got
    pre = next(e for e in engine.trace_events() if e[3] == EV_PREFILL_PREEMPT)
    assert pre[4] == long.request_id           # the long prompt was parked...
    assert pre[5][1] == tight.request_id       # ...by the tight arrival
    res = next(e for e in engine.trace_events() if e[3] == EV_PREFILL_RESUME)
    assert res[4] == long.request_id and res[5][0] > 0
    # stall attribution: the long prompt's parked ticks are stalls, and the
    # identity still holds exactly
    rec = next(r for r in engine.monitor.completed
               if r.request_id == long.request_id)
    total = (rec.phase_queued + rec.phase_prefill + rec.phase_decode
             + rec.phase_stall)
    assert total == pytest.approx(rec.latency)
    assert rec.phase_stall > 0.0


# ---------------------------------------------------------- flight recorder
def test_flight_dump_on_fail_worker(engine_factory, trace_factory):
    engine = engine_factory(n_pairs=2, trace="flight")
    for r in trace_factory("bursty", n=4):
        engine.submit(r)
    engine.step()
    engine.fail_worker(0)
    assert len(engine.flight_dumps) == 1
    dump = engine.flight_dumps[0]
    assert dump["reason"] == "fail_worker" and dump["events"]
    assert any(row[3] == "worker_fail" for row in dump["events"])
    engine.run_until_done()


def test_flight_dump_on_engine_exception(engine_factory, trace_factory, tmp_path):
    engine = engine_factory(trace="on", trace_dir=str(tmp_path))
    for r in trace_factory("bursty", n=2):
        engine.submit(r)
    engine.step()

    def boom(now):
        raise RuntimeError("injected decode fault")

    engine.pairs[0].decode_iteration = boom
    with pytest.raises(RuntimeError, match="injected decode fault"):
        engine.step()
    assert engine.flight_dumps and engine.flight_dumps[-1]["reason"] == "engine_exception"
    assert engine.flight_dumps[-1]["events"]
    written = list(tmp_path.glob("flight_engine_exception_*.json"))
    assert len(written) == 1
    assert json.load(open(written[0]))["events"]


def test_traceview_cli_renders_dump(engine_factory, trace_factory, tmp_path, capsys):
    from tools.traceview.cli import main as traceview_main

    engine = engine_factory(n_pairs=2, trace="on")
    serve_all(engine, trace_factory("bursty", n=4))
    path = tmp_path / "dump.json"
    path.write_text(json.dumps(engine.trace.to_dump("manual", engine._now)))
    assert traceview_main([str(path), "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "slowest requests" in out and "per-worker occupancy" in out
    assert "decode_step" in out
    # bad input: clean error, not a traceback
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert traceview_main([str(bad)]) == 1


# ------------------------------------------------------------------ config
def test_config_trace_knobs():
    from repro.api.config import ServeConfig

    cfg = ServeConfig.reduced_smoke(trace="on", trace_capacity=128)
    econf = cfg.build_engine_config()
    assert econf.trace == "on" and econf.trace_capacity == 128
    assert ServeConfig.reduced_smoke().build_engine_config().trace == "off"
    with pytest.raises(ValueError, match="trace must be"):
        ServeConfig.reduced_smoke(trace="maybe")
    with pytest.raises(ValueError, match="trace_capacity"):
        ServeConfig.reduced_smoke(trace_capacity=0)
    # round-trips like every other knob
    assert ServeConfig.from_dict(cfg.to_dict()) == cfg


def test_frontend_observability_surface():
    from repro.api.config import ServeConfig
    from repro.api.frontend import StreamServe

    serve = StreamServe(ServeConfig.reduced_smoke(trace="on", n_pairs=1))
    h = serve.submit([1, 2, 3, 4])
    h.result()
    assert serve.trace_events()
    assert serve.export_chrome_trace()["traceEvents"]
    assert "streamserve_tokens_generated_total" in serve.prometheus_text()
    assert serve.flight_dumps == []
