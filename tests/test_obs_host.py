"""The program's host-side observation: phase spans on the profiler clock,
wall-clock request stamps and the work counters.

* ``ss.*`` spans recorded by a CPU ``jax.profiler`` session: every name
  appears, every span but ``ss.submit`` lies inside an ``ss.step``, and
  ``ss.draft`` appears exactly on the pairs' verify steps (structure, not
  timing)
* ``engine.counters()`` against a plain recount of the same run's prompts,
  buckets and StreamTrace events, on the bucketed, chunked and paged paths,
  and their Prometheus export
* ``Request.w_*`` stamps: set and ordered on each admission path, and read
  by no decision (outputs and tick-time trace dumps do not change when the
  stamps are absent or garbled)
"""
import glob
import itertools

import jax
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import scheduler as scheduler_mod
from repro.core.engine import EngineConfig, PipeServeEngine
from repro.obs.counters import COUNTER_HELP
from repro.obs.trace import (
    EV_DECODE_STEP,
    EV_PREFILL_CHUNK,
    EV_PREFILL_END,
    EV_PREFILL_START,
    EV_VERIFY,
    SPAN_DRAFT,
    SPAN_NAMES,
    SPAN_STEP,
    SPAN_SUBMIT,
)
from repro.serving.request import Request, SamplingParams

PAGED = {"paged_kv": True, "kv_blocks": 256, "kv_block_size": 16}
PATHS = {"bucketed": {}, "chunked": {"prefill_chunk": 16}, "paged": PAGED}


def _host_spans(trace_dir):
    """``(start_ns, end_ns, name)`` of the ``ss.*`` events on the host plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events if e.name.startswith("ss."))
    return sorted(out)


def _profiled(engine, reqs, trace_dir, n_steps):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        for r in reqs:
            engine.submit(r)
        for _ in range(n_steps):
            engine.step()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(trace_dir)


@pytest.mark.parametrize("draft", ["ngram", "none"])
def test_phase_spans_nest_in_steps(engine_factory, trace_factory, tmp_path, draft):
    engine = engine_factory(n_pairs=2 if draft == "ngram" else 1, trace="on", draft=draft)
    spans = _profiled(engine, trace_factory("bursty", n=6, seed=2, max_new=12),
                      tmp_path, n_steps=8)
    steps = [(s, e) for s, e, n in spans if n == SPAN_STEP]
    assert len(steps) == 8
    names = {n for _, _, n in spans}
    want = set(SPAN_NAMES) if draft == "ngram" else set(SPAN_NAMES) - {SPAN_DRAFT}
    assert names == want
    drafts_per_step = [0] * len(steps)
    for s, e, n in spans:
        if n in (SPAN_STEP, SPAN_SUBMIT):
            continue
        inside = [i for i, (a, b) in enumerate(steps) if a <= s and e <= b]
        assert len(inside) == 1, f"{n} at {s} lies in no ss.step"
        if n == SPAN_DRAFT:
            drafts_per_step[inside[0]] += 1
    # ss.draft exactly on verify steps: one per pair that verified that tick
    verifies = [0] * len(steps)
    for ev in engine.trace_events():
        if ev[3] == EV_VERIFY:
            verifies[int(ev[1]) - 1] += 1
    assert drafts_per_step == verifies
    assert (sum(verifies) > 0) == (draft == "ngram")


def _pow2_bucket(n, lo, hi):
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


def _recount_prefill(engine, reqs, path):
    """(calls, live tokens, computed positions) from the run's own events
    and the buckets the engine's configuration implies."""
    econf = engine.econf
    events = engine.trace_events()
    if path == "chunked":
        pair = engine.pairs[0]
        chunks = [e[5][1] for e in events if e[3] == EV_PREFILL_CHUNK]
        return len(chunks), sum(chunks), len(chunks) * len(pair.chunk_rows) * pair._chunk
    prompt = {r.request_id: len(r.prompt) for r in reqs}
    hit = {e[4]: e[5][1] for e in events if e[3] == EV_PREFILL_START}
    calls = live = computed = 0
    ends = [e for e in events if e[3] == EV_PREFILL_END]
    i = 0
    while i < len(ends):   # one admit call = `fused` consecutive end events
        fused = ends[i][5][0]
        group = [ends[j][4] for j in range(i, i + fused)]
        i += fused
        lens = [prompt[rid] - hit[rid] for rid in group]
        S = _pow2_bucket(max(lens), econf.prefill_bucket_min, econf.max_len)
        rows = econf.max_batch if path == "paged" else _pow2_bucket(fused, 1, econf.admit_batch)
        calls += 1
        live += sum(lens)
        computed += rows * S
    return calls, live, computed


@pytest.mark.parametrize("path", sorted(PATHS))
def test_work_counters_match_recount(engine_factory, trace_factory, path):
    engine = engine_factory(n_pairs=2, trace="on", **PATHS[path])
    if path == "chunked":   # the cheapest warm-up: one chunk program
        engine.warmup()
        assert engine.counters()["prefill_calls"] == 0   # warm-up is not work
    reqs = trace_factory("bursty", n=6, seed=4, max_new=10)
    for r in reqs:
        engine.submit(r)
    n_steps = 0
    while not engine.drained():
        engine.step()
        n_steps += 1
    c = engine.counters()
    assert [p["steps"] for p in c["pairs"]] == [n_steps, n_steps]
    assert set(c) == set(COUNTER_HELP) | {"pairs"}
    assert all(c[k] == sum(p[k] for p in c["pairs"]) for k in COUNTER_HELP)
    calls, live, computed = _recount_prefill(engine, reqs, path)
    assert (c["prefill_calls"], c["prefill_live_tokens"], c["prefill_slot_tokens"]) \
        == (calls, live, computed)
    assert 0 < c["prefill_live_tokens"] <= c["prefill_slot_tokens"]
    steps = [e[5] for e in engine.trace_events() if e[3] == EV_DECODE_STEP]
    assert c["spec_proposed"] == sum(sum(p[5]) for p in steps)
    assert c["spec_accepted"] == sum(sum(p[6]) for p in steps)
    assert c["verify_calls"] == sum(1 for p in steps if p[1] > 0) > 0
    assert c["decode_calls"] == sum(1 for p in steps if p[1] == 0)
    txt = engine.prometheus_text()
    for k in COUNTER_HELP:
        for w, p in enumerate(c["pairs"]):
            assert f'streamserve_{k}_total{{worker="{w}"}} {p[k]}\n' in txt


@pytest.mark.parametrize("path", sorted(PATHS))
def test_wall_stamps_set_and_ordered(engine_factory, trace_factory, path):
    engine = engine_factory(**PATHS[path])
    reqs = trace_factory("bursty", n=6, seed=6, max_new=6)
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    for r in reqs:
        assert r.state.value == "finished"
        assert r.w_submit <= r.w_prefill_start <= r.w_first_token


def _normalized_run(tiny_model, path):
    """Outputs and the id-normalised tick-time trace dump of one seeded run
    of requests with alternating tight and relaxed SLOs."""
    cfg, params = tiny_model
    engine = PipeServeEngine(cfg, params, n_pairs=2, econf=EngineConfig(
        max_batch=2, max_len=96, trace="on", **PATHS[path]))
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(6):
        n = int(rng.integers(6, 50))
        reqs.append(Request(prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                            params=SamplingParams(max_new_tokens=8),
                            slo_ttft=4.0 if i % 2 == 0 else 100.0,
                            slo_tpot=0.25 if i % 2 == 0 else 8.0))
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    order = {r.request_id: f"req#{i}" for i, r in enumerate(reqs)}
    events = [[order.get(x, x) if isinstance(x, str) else x for x in ev]
              for ev in engine.trace.to_dump("end", engine._now)["events"]]
    return [list(r.output_tokens) for r in reqs], events


STEER_PATHS = ("bucketed", "chunked")


@pytest.fixture(scope="module")
def stamped_runs(tiny_model):
    return [_normalized_run(tiny_model, p) for p in STEER_PATHS]


@pytest.mark.parametrize("stamps", ["absent", "garbled"])
def test_wall_stamps_steer_nothing(tiny_model, stamped_runs, monkeypatch, stamps):
    """Outputs and tick-time trace dumps are the same whatever the wall
    clock reads: no routing, scheduling or depth decision looks at it."""
    if stamps == "absent":
        clock = lambda: None  # noqa: E731
    else:
        ticks = itertools.count()
        clock = lambda: (-1.0) ** next(ticks) * 1e9 / (1 + next(ticks))  # noqa: E731
    monkeypatch.setattr(engine_mod, "perf_counter", clock)
    monkeypatch.setattr(scheduler_mod, "perf_counter", clock)
    got = [_normalized_run(tiny_model, p) for p in STEER_PATHS]
    assert got == stamped_runs
