"""End-to-end integration tests of PipeServeEngine (real JAX execution)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import EngineConfig, PipeServeEngine
from repro.core.flowguard import RoundRobinRouter
from repro.distributed.sharding import unzip_params
from repro.models import build_model
from repro.serving.request import Request, RequestState, SamplingParams


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced_config("qwen3-1.7b")
    cfg = dataclasses.replace(cfg, n_layers=2)
    model = build_model(cfg)
    params, _ = unzip_params(model.init(jax.random.PRNGKey(0)))
    return cfg, params


def _requests(cfg, n, rng, max_new=8, plen=10, shared=None):
    out = []
    shared = shared or []
    for _ in range(n):
        body = rng.integers(0, cfg.vocab_size, plen - len(shared)).tolist()
        out.append(
            Request(prompt=list(shared) + body,
                    params=SamplingParams(max_new_tokens=max_new))
        )
    return out


def test_engine_completes_all_requests(small_model):
    cfg, params = small_model
    eng = PipeServeEngine(cfg, params, n_pairs=2,
                          econf=EngineConfig(max_batch=3, max_len=96))
    rng = np.random.default_rng(0)
    reqs = _requests(cfg, 7, rng)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_steps=800)
    assert len(eng.monitor.completed) == 7
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert len(r.output_tokens) == 8
        assert all(0 <= t < cfg.vocab_size for t in r.output_tokens)


def test_engine_deterministic_greedy(small_model):
    """Same trace twice -> identical outputs (single-controller determinism)."""
    cfg, params = small_model

    def run():
        eng = PipeServeEngine(cfg, params, n_pairs=2,
                              econf=EngineConfig(max_batch=2, max_len=96))
        rng = np.random.default_rng(1)
        reqs = _requests(cfg, 4, rng)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_steps=800)
        return [tuple(r.output_tokens) for r in reqs]

    assert run() == run()


def test_speculation_preserves_greedy_outputs(small_model):
    """Greedy speculative decode must emit EXACTLY the plain-autoregressive
    tokens (lossless acceleration — the core speculative-decoding property),
    regardless of draft quality."""
    cfg, params = small_model

    def run(draft):
        eng = PipeServeEngine(
            cfg, params, n_pairs=1,
            econf=EngineConfig(max_batch=2, max_len=96, draft=draft,
                               adaptive=False, fixed_depth=0 if draft == "none" else 4),
        )
        rng = np.random.default_rng(2)
        reqs = _requests(cfg, 2, rng, max_new=10, plen=12)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_steps=800)
        return [tuple(r.output_tokens) for r in reqs]

    plain = run("none")
    spec = run("ngram")
    assert plain == spec


def test_flowguard_routes_to_both_pairs(small_model):
    cfg, params = small_model
    eng = PipeServeEngine(cfg, params, n_pairs=2,
                          econf=EngineConfig(max_batch=2, max_len=96))
    rng = np.random.default_rng(3)
    for r in _requests(cfg, 6, rng):
        eng.submit(r)
    eng.run_until_done(max_steps=900)
    workers = {r.worker_id for r in eng.monitor.completed}
    assert workers == {0, 1}


def test_worker_failure_reroutes_and_completes(small_model):
    cfg, params = small_model
    eng = PipeServeEngine(cfg, params, n_pairs=2,
                          econf=EngineConfig(max_batch=2, max_len=96))
    rng = np.random.default_rng(4)
    reqs = _requests(cfg, 6, rng)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    n = eng.fail_worker(1)
    assert n >= 0
    eng.run_until_done(max_steps=1200)
    assert len(eng.monitor.completed) == 6
    assert all(r.worker_id == 0 for r in eng.monitor.completed)


def test_prefix_cache_hit_rate_signal(small_model):
    """Shared-prefix requests must raise C_w (the FlowGuard cache signal)."""
    cfg, params = small_model
    eng = PipeServeEngine(
        cfg, params, n_pairs=1,
        econf=EngineConfig(max_batch=2, max_len=96, kv_blocks=512, kv_block_size=4),
    )
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 8).tolist()
    for r in _requests(cfg, 5, rng, plen=12, shared=shared):
        eng.submit(r)
    eng.run_until_done(max_steps=800)
    assert eng.monitor.workers[0].cache_hit_rate > 0.2


def test_round_robin_router_alternates(small_model):
    cfg, params = small_model
    eng = PipeServeEngine(cfg, params, n_pairs=2, router=RoundRobinRouter(),
                          econf=EngineConfig(max_batch=2, max_len=96))
    rng = np.random.default_rng(6)
    for r in _requests(cfg, 4, rng):
        eng.submit(r)
    assert [w for _, w in eng.scheduler.routing_log] == [0, 1, 0, 1]


def test_adaptive_depth_responds_to_acceptance(small_model):
    """After decode iterations the SpecuStream depth reflects the measured
    acceptance (closed loop through the monitor)."""
    cfg, params = small_model
    eng = PipeServeEngine(cfg, params, n_pairs=1,
                          econf=EngineConfig(max_batch=4, max_len=96, draft="ngram"))
    rng = np.random.default_rng(7)
    for r in _requests(cfg, 4, rng, max_new=12):
        eng.submit(r)
    eng.run_until_done(max_steps=800)
    d = eng.pairs[0].spec.last_decision
    assert d is not None and d.bucket_depth >= 2


def test_reset_cache_reuses_the_old_buffers(small_model):
    """Warm-up ends with ``reset_cache``: the empty cache is written into the
    donated old one, never allocated beside it (the old buffers outlive the
    steps queued on them, so both would share the device)."""
    from repro.core.engine import ModelLane

    cfg, params = small_model
    for paged in (False, True):
        lane = ModelLane(cfg, params, 2, 32, paged=paged, kv_blocks=8,
                         kv_block_size=8)
        lane.decode(jax.numpy.ones((2, 3), jax.numpy.int32))
        old = jax.tree.leaves(lane.cache)
        lane.reset_cache()
        assert all(a.is_deleted() for a in old)
        init, sizes = lane._cache_init()
        fresh = init(*sizes)
        for got, want in zip(jax.tree.leaves(lane.cache), jax.tree.leaves(fresh),
                             strict=True):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
