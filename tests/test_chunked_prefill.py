"""Chunked prefill with preemption: one compiled prefill shape regardless of
prompt length, bit-identical greedy outputs vs the one-shot bucketed path,
EDF preemption at chunk boundaries (tight-deadline short prompts jump a long
prompt's chunks), and clean cancel / fault behaviour for parked partials."""
import numpy as np
import pytest

from repro.core import EngineConfig, PipeServeEngine
from repro.serving.request import Request, RequestState, SamplingParams


def _outputs(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run_until_done(max_steps=2000)
    return [tuple(r.output_tokens) for r in reqs]


def test_chunked_greedy_bit_identical(engine_factory, trace_factory):
    """Chunk-at-a-time prefill must emit EXACTLY the tokens of both the
    bucketed and the legacy one-shot paths (greedy)."""
    runs = {}
    for name, kw in {
        "chunked": {"prefill_chunk": 16},
        "bucketed": {},
        "legacy": {"prefill_buckets": False, "verify_buckets": None},
    }.items():
        runs[name] = _outputs(engine_factory(**kw), trace_factory("bursty", n=5))
    assert runs["chunked"] == runs["bucketed"] == runs["legacy"]


def test_single_prefill_trace_regardless_of_length(engine_factory, tiny_model):
    """Short and near-max_len prompts must share ONE compiled chunk step;
    the bucketed prefill family must never be traced."""
    cfg, _ = tiny_model
    eng = engine_factory(prefill_chunk=16, max_batch=3)
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                params=SamplingParams(max_new_tokens=4))
        for plen in (6, 16, 17, 40, 80)  # below / at / above / multi-chunk
    ]
    _outputs(eng, reqs)
    sizes = eng.jit_cache_sizes()
    # one compiled chunk program per lane (the static model closure keys the
    # module-level jit cache) regardless of prompt length
    assert sizes["chunk_prefill"] == len(eng.pairs)
    assert sizes["lane_prefill"] == 0  # one-shot path never compiled


def test_zero_retraces_after_warmup(engine_factory, tiny_model):
    """Steady-state serving with prefill_chunk on must not grow any jit
    cache after warmup() — the chunked hot-path contract."""
    cfg, _ = tiny_model
    eng = engine_factory(prefill_chunk=16, max_batch=3)
    eng.warmup(max_prompt_len=60)
    before = eng.jit_cache_sizes()
    rng = np.random.default_rng(3)
    for _ in range(15):
        eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(6, 60))).tolist(),
            params=SamplingParams(max_new_tokens=int(rng.integers(4, 10))),
        ))
    eng.run_until_done(max_steps=2000)
    assert len(eng.monitor.completed) == 15
    after = eng.jit_cache_sizes()
    grew = {n: (before[n], after[n]) for n in after if after[n] != before.get(n)}
    assert not grew, f"steady-state retraces: {grew}"


def _long_short(cfg, rng, long_len=60, short_len=8, slo_ttft=30.0):
    long = Request(prompt=rng.integers(0, cfg.vocab_size, long_len).tolist(),
                   params=SamplingParams(max_new_tokens=6))
    short = Request(prompt=rng.integers(0, cfg.vocab_size, short_len).tolist(),
                    params=SamplingParams(max_new_tokens=6), slo_ttft=slo_ttft)
    return long, short


def test_preempt_and_resume(engine_factory, tiny_model):
    """A tight-SLO short prompt arriving mid-prefill parks the long prompt
    (PREFILLING, chunk cursor frozen), gets its first token first, and the
    long prompt resumes chunk-aligned — both with correct outputs."""
    cfg, _ = tiny_model

    def run(preempt):
        eng = engine_factory(prefill_chunk=8, prefill_preempt=preempt)
        rng = np.random.default_rng(7)
        long, short = _long_short(cfg, rng)
        eng.submit(long)
        eng.step()  # long ingests its first chunk
        cursor_before = eng.chunk_progress()[long.request_id]
        assert long.state == RequestState.PREFILLING and 0 < cursor_before < 60
        eng.submit(short)
        eng.step()  # preemption point: EDF picks the short's deadline
        if preempt:
            # the long prompt is parked with its partial progress intact
            assert long.state == RequestState.PREFILLING
            assert eng.chunk_progress()[long.request_id] == cursor_before
        eng.run_until_done(max_steps=400)
        return long, short

    long_p, short_p = run(True)
    ttft = lambda r: r.token_times[0] - r.arrival_time  # noqa: E731
    assert ttft(short_p) < ttft(long_p)  # the short jumped the long's chunks

    long_f, short_f = run(False)
    assert ttft(short_f) >= ttft(long_f)  # run-to-completion: short waited
    assert ttft(short_p) < ttft(short_f)  # preemption bought the short TTFT
    # scheduling order must never change the tokens (greedy determinism)
    assert long_p.output_tokens == long_f.output_tokens
    assert short_p.output_tokens == short_f.output_tokens
    # and both match the un-chunked engine's outputs
    eng = engine_factory()
    rng = np.random.default_rng(7)
    long_ref, short_ref = _long_short(cfg, rng)
    outs = _outputs(eng, [long_ref, short_ref])
    assert outs == [tuple(long_p.output_tokens), tuple(short_p.output_tokens)]


def test_chunk_clamped_to_capacity_divisor(tiny_model):
    """A chunk that doesn't divide the cache capacity would let the final
    (padding-rewound) write window wrap the ring and clobber the prompt head
    — the engine must clamp to a divisor and stay bit-identical."""
    cfg, params = tiny_model
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab_size, 97).tolist()  # non-aligned length

    def run(**kw):
        eng = PipeServeEngine(cfg, params, n_pairs=1,
                              econf=EngineConfig(max_batch=2, max_len=100, **kw))
        req = Request(prompt=list(prompt), params=SamplingParams(max_new_tokens=3))
        eng.submit(req)
        eng.run_until_done(max_steps=200)
        return eng, tuple(req.output_tokens)

    eng, chunked = run(prefill_chunk=48)  # 48 does not divide cap=100
    assert 100 % eng.pairs[0]._chunk == 0  # clamped to a divisor
    _, bucketed = run()
    assert chunked == bucketed


def test_chunk_clamped_for_sliding_window(tiny_model):
    """Sliding-window ring caches only tolerate SPEC_MARGIN in-step writes
    before live window entries get evicted — the chunk must clamp to it."""
    import dataclasses as dc

    import jax

    from repro.distributed.sharding import unzip_params
    from repro.models import build_model
    from repro.models.attention import SPEC_MARGIN

    cfg, _ = tiny_model
    swa = dc.replace(cfg, sliding_window=64, name=cfg.name + "-swa")
    params, _ = unzip_params(build_model(swa).init(jax.random.PRNGKey(2)))
    rng = np.random.default_rng(29)
    prompt = rng.integers(0, swa.vocab_size, 150).tolist()  # crosses the window

    def run(**kw):
        eng = PipeServeEngine(swa, params, n_pairs=1,
                              econf=EngineConfig(max_batch=2, max_len=192, **kw))
        req = Request(prompt=list(prompt), params=SamplingParams(max_new_tokens=3))
        eng.submit(req)
        eng.run_until_done(max_steps=200)
        return eng, tuple(req.output_tokens)

    eng, chunked = run(prefill_chunk=48)  # 48 > SPEC_MARGIN would clobber
    assert eng.pairs[0]._chunk <= SPEC_MARGIN
    _, bucketed = run()
    assert chunked == bucketed


def test_routing_sees_parked_chunk_backlog(engine_factory, tiny_model):
    """A request parked in a chunk row has left the prefill queue but still
    owes the lane one tick per remaining chunk — queue_delay/queue_depth
    must price it, or FlowGuard routes to a saturated lane as if idle."""
    cfg, _ = tiny_model
    eng = engine_factory(prefill_chunk=8)
    rng = np.random.default_rng(31)
    req = Request(prompt=rng.integers(0, cfg.vocab_size, 60).tolist(),
                  params=SamplingParams(max_new_tokens=4))
    eng.submit(req)
    eng.step()  # parked: 8 of 60 tokens ingested, queue empty
    sched = eng.scheduler
    assert len(sched.prefill_queues[0]) == 0
    assert sched.queue_depth(0) == 1  # the parked request is visible
    assert sched.queue_delay(0) == 7.0  # ceil((60 - 8) / 8) remaining chunks
    eng.run_until_done(max_steps=200)
    assert sched.queue_depth(0) == 0 and sched.queue_delay(0) == 0.0


def test_warmup_refuses_mid_chunk_prefill(engine_factory, tiny_model):
    """warmup() resets the chunk cache — calling it while a partial prefill
    is parked would silently wipe the parked KV; it must refuse."""
    cfg, _ = tiny_model
    eng = engine_factory(prefill_chunk=8)
    rng = np.random.default_rng(37)
    eng.submit(Request(prompt=rng.integers(0, cfg.vocab_size, 40).tolist(),
                       params=SamplingParams(max_new_tokens=4)))
    eng.step()
    assert eng.pairs[0].prefill_in_flight() == 1
    with pytest.raises(AssertionError, match="warmup"):
        eng.warmup()


def test_cancel_parked_chunk_request(engine_factory, tiny_model):
    cfg, _ = tiny_model
    eng = engine_factory(prefill_chunk=8)
    rng = np.random.default_rng(9)
    req = Request(prompt=rng.integers(0, cfg.vocab_size, 40).tolist(),
                  params=SamplingParams(max_new_tokens=4))
    eng.submit(req)
    eng.step()
    assert req.state == RequestState.PREFILLING
    assert eng.cancel(req.request_id)
    assert req.state == RequestState.CANCELLED
    rec = eng.monitor.completed[-1]
    assert rec.request_id == req.request_id and rec.cancelled
    assert req.request_id not in eng.pairs[0].kv.seqs  # KV released
    assert req.request_id not in eng.chunk_progress()
    assert eng.drained()


def test_fail_worker_reroutes_chunk_in_flight(engine_factory, tiny_model):
    """A pair dying mid-chunked-prefill re-routes its parked partials; they
    restart from scratch on the survivor and still complete."""
    cfg, _ = tiny_model
    eng = engine_factory(n_pairs=2, prefill_chunk=8)
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 40).tolist(),
                    params=SamplingParams(max_new_tokens=4)) for _ in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    victim = next(p.worker_id for p in eng.pairs if p.prefill_in_flight())
    eng.fail_worker(victim)
    eng.run_until_done(max_steps=800)
    assert len(eng.monitor.completed) == 4
    assert all(r.worker_id != victim for r in eng.monitor.completed)


def test_last_worker_death_fails_chunk_orphans_cleanly(engine_factory, tiny_model):
    """No healthy worker left: queued AND parked requests FAIL terminally
    with records instead of raising mid-loop / being dropped silently."""
    cfg, _ = tiny_model
    eng = engine_factory(prefill_chunk=8)
    rng = np.random.default_rng(13)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 40).tolist(),
                    params=SamplingParams(max_new_tokens=4)) for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.fail_worker(0)  # the only pair
    assert all(r.state == RequestState.FAILED for r in reqs)
    assert all(r.error == "no_healthy_workers" for r in reqs)
    assert len(eng.monitor.completed) == 3  # every orphan got a record


def test_model_draft_incompatible_with_chunking(tiny_model):
    """The small-transformer draft mirrors bucketed admission state, which
    chunked prefill bypasses — constructing that combination must fail fast."""
    import dataclasses as dc

    cfg, params = tiny_model
    draft_cfg = dc.replace(cfg, n_layers=1, name=cfg.name + "-draft")
    from repro.models import build_model
    import jax

    from repro.distributed.sharding import unzip_params

    draft_params, _ = unzip_params(build_model(draft_cfg).init(jax.random.PRNGKey(1)))
    with pytest.raises(ValueError, match="prefill_chunk"):
        PipeServeEngine(
            cfg, params, n_pairs=1,
            econf=EngineConfig(max_batch=2, max_len=96, draft="model",
                               prefill_chunk=16),
            draft_cfg=draft_cfg, draft_params=draft_params,
        )


def test_estimator_chunk_pricing(tiny_model):
    """Chunked service is quantised at one chunk per tick — the queue-delay
    estimate FlowGuard routes on must reflect ceil(prompt / chunk)."""
    from repro.serving.cost_model import CostModel, PrefillDelayEstimator

    cfg, _ = tiny_model
    est = PrefillDelayEstimator(cfg, prefill_chunk=16)

    def mk(n):
        return Request(prompt=list(range(n)))

    assert est.ticks(mk(8)) == 1.0
    assert est.ticks(mk(16)) == 1.0
    assert est.ticks(mk(17)) == 2.0
    assert est.ticks(mk(80)) == 5.0
    # cost-model chunk pricing: a single chunk covering the whole prompt
    # degenerates to one-shot prefill; finer chunks pay per-chunk dispatch
    cm = CostModel(cfg)
    assert cm.chunked_prefill_time(512, 512) == pytest.approx(cm.prefill_time(512))
    assert cm.chunked_prefill_time(512, 8) >= 64 * cm.hw.dispatch_overhead
    assert cm.chunked_prefill_time(0, 128) == cm.hw.dispatch_overhead


def test_device_profile_lookup():
    """Peaks come from the device the program runs on: a listed kind
    resolves to its row, the CPU backend to the documented v5e stand-in, and
    an unlisted accelerator raises instead of borrowing another chip's
    peaks."""
    import types

    import jax

    from repro.serving.cost_model import PEAKS, TPU_V5E, device_profile

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_profile(v5e) is PEAKS["TPU v5 lite"]
    assert TPU_V5E.peak_flops == 197e12 and TPU_V5E.hbm_bw == 819e9
    assert device_profile(jax.devices("cpu")[0]) is TPU_V5E
    with pytest.raises(ValueError, match="no peak rates"):
        device_profile(types.SimpleNamespace(platform="gpu", device_kind="H100"))


def test_serveconfig_chunk_knobs_round_trip():
    from repro.api import ServeConfig

    cfg = ServeConfig.reduced_smoke(prefill_chunk=32, prefill_preempt=False)
    again = ServeConfig.from_yaml(cfg.to_yaml())
    assert again.prefill_chunk == 32 and again.prefill_preempt is False
    econf = again.build_engine_config()
    assert econf.prefill_chunk == 32 and econf.prefill_preempt is False
    assert ServeConfig.reduced_smoke().prefill_chunk is None  # default off
    with pytest.raises(ValueError):
        ServeConfig.reduced_smoke(prefill_chunk=4)  # < 8
    with pytest.raises(ValueError):
        ServeConfig.reduced_smoke(prefill_chunk=128)  # > max_len (96)
    with pytest.raises(ValueError):
        ServeConfig.reduced_smoke(prefill_preempt="yes")
