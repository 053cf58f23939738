"""Paged KV decode, radix prefix reuse, and prefix-hit routing.

Covers the four layers of the paged path: kernel parity (ref-paged vs dense
ref, Pallas-interpret vs ref), engine bit-identity vs the dense path with
zero steady-state retraces, prefix-hit admission + holder-affine routing,
and continuous batching under page-pool pressure (evict/requeue vs the
legacy truncate knob).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_paged_pallas
from repro.serving.kv_cache import KVCacheManager, chain_hashes

RNG = np.random.default_rng(11)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


def _paged_case(B=2, T=3, P=4, ps=16, K=2, D=32, n_pages=32):
    """Dense K/V plus an equivalent shuffled page-pool layout."""
    H = 2 * K
    S = P * ps
    q = _rand((B, T, H, D))
    k = _rand((B, S, K, D))
    v = _rand((B, S, K, D))
    cache_len = jnp.asarray(RNG.integers(T + 1, S, size=(B,)), jnp.int32)
    # scatter each row's pages to distinct shuffled pool slots; leave a
    # ragged tail of the table unallocated (-1) past the valid length
    perm = RNG.permutation(n_pages)[: B * P].reshape(B, P)
    k_pool = jnp.asarray(RNG.normal(size=(n_pages, K, ps, D)), jnp.float32)
    v_pool = jnp.asarray(RNG.normal(size=(n_pages, K, ps, D)), jnp.float32)
    bt = np.full((B, P), -1, np.int32)
    for b in range(B):
        pages_live = -(-int(cache_len[b]) // ps)
        for i in range(pages_live):
            bt[b, i] = perm[b, i]
            # the pool is head-major: (K, ps, D) per page
            k_pool = k_pool.at[perm[b, i]].set(k[b, i * ps : (i + 1) * ps].swapaxes(0, 1))
            v_pool = v_pool.at[perm[b, i]].set(v[b, i * ps : (i + 1) * ps].swapaxes(0, 1))
    return q, k, v, k_pool, v_pool, cache_len, jnp.asarray(bt)


def test_ref_paged_matches_dense_ref():
    q, k, v, k_pool, v_pool, cache_len, bt = _paged_case()
    want = ref.decode_attention(q, k, v, cache_len)
    got = ref.decode_attention_paged(q, k_pool, v_pool, cache_len, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_paged_pallas_matches_ref():
    q, _, _, k_pool, v_pool, cache_len, bt = _paged_case()
    want = ref.decode_attention_paged(q, k_pool, v_pool, cache_len, bt)
    got = decode_attention_paged_pallas(
        q, k_pool, v_pool, cache_len, bt, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# engine bit-identity + zero retraces
# ---------------------------------------------------------------------------

PAGED = {"paged_kv": True, "kv_blocks": 256, "kv_block_size": 16}


def _serve(engine, reqs):
    engine.warmup()
    pre = engine.jit_cache_sizes()
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    post = engine.jit_cache_sizes()
    retraces = {k: post[k] - pre.get(k, 0) for k in post if post[k] != pre.get(k, 0)}
    return [list(r.output_tokens) for r in reqs], retraces


def test_paged_engine_matches_dense_greedy(engine_factory, trace_factory):
    dense_out, _ = _serve(engine_factory(), trace_factory("bursty"))
    paged_out, retraces = _serve(engine_factory(**PAGED), trace_factory("bursty"))
    assert paged_out == dense_out
    assert retraces == {}, f"steady-state retraces with paging: {retraces}"


def test_paged_chunked_engine_matches_dense_chunked(engine_factory, trace_factory):
    dense_out, _ = _serve(
        engine_factory(prefill_chunk=16), trace_factory("bursty")
    )
    paged_out, retraces = _serve(
        engine_factory(prefill_chunk=16, **PAGED), trace_factory("bursty")
    )
    assert paged_out == dense_out
    assert retraces == {}, f"steady-state retraces with paging: {retraces}"


# ---------------------------------------------------------------------------
# prefix reuse + routing
# ---------------------------------------------------------------------------


def test_prefix_hit_skips_prefill_and_matches(engine_factory, trace_factory):
    """A re-submitted prompt consumes resident pages (cache_hit_tokens > 0)
    and still decodes the exact same greedy continuation."""
    eng = engine_factory(**PAGED)
    eng.warmup()
    first = trace_factory("bursty", n=1, lo=40, hi=41)[0]
    eng.submit(first)
    eng.run_until_done()
    assert first.cache_hit_tokens == 0
    second = trace_factory("bursty", n=1, lo=40, hi=41)[0]  # same seed: same prompt
    assert list(second.prompt) == list(first.prompt)
    eng.submit(second)
    eng.run_until_done()
    # 40-token prompt, 16-token pages, >=1 recomputed token: 2 shared pages
    assert second.cache_hit_tokens == 32
    assert second.output_tokens == first.output_tokens


def test_prefix_hit_routes_to_holding_worker(engine_factory, trace_factory):
    """FlowGuard's prefix term steers a re-submitted prefix to the pair whose
    pool still holds it, even though serving it tilted every other signal
    (hit-rate EMA, throughput) against that pair."""
    eng = engine_factory(n_pairs=2, **PAGED)
    eng.warmup()
    first = trace_factory("bursty", n=1, lo=40, hi=41)[0]
    eng.submit(first)
    eng.run_until_done()
    holder = eng.scheduler.routing_log[-1][1]
    second = trace_factory("bursty", n=1, seed=0, lo=40, hi=41)[0]
    eng.submit(second)
    eng.run_until_done()
    assert eng.scheduler.routing_log[-1] == (second.request_id, holder)
    assert second.cache_hit_tokens > 0
    assert second.output_tokens == first.output_tokens


def test_prefix_probe_scores_only_holder(engine_factory, trace_factory):
    eng = engine_factory(n_pairs=2, **PAGED)
    eng.warmup()
    req = trace_factory("bursty", n=1, lo=40, hi=41)[0]
    eng.submit(req)
    eng.run_until_done()
    holder = eng.scheduler.routing_log[-1][1]
    probe = trace_factory("bursty", n=1, lo=40, hi=41)[0]
    scores = {w: eng._prefix_score(w, probe) for w in (0, 1)}
    assert scores[holder] > 0.0
    assert scores[1 - holder] == 0.0


# ---------------------------------------------------------------------------
# continuous batching under page pressure
# ---------------------------------------------------------------------------

TINY_POOL = {"paged_kv": True, "kv_blocks": 7, "kv_block_size": 16}


def _pressure_trace(trace_factory, n=4):
    # long-ish prompts + enough generation to outgrow a 7-page pool with two
    # 2-3-page sequences resident
    return trace_factory("bursty", n=n, lo=24, hi=33, max_new=24)


def test_pool_exhaustion_evicts_and_requeues(engine_factory, trace_factory):
    eng = engine_factory(**TINY_POOL)
    eng.warmup()
    reqs = _pressure_trace(trace_factory)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    recs = {r.request_id: r for r in eng.monitor.completed}
    assert len(recs) == len(reqs)
    assert any(r.kv_requeued > 0 for r in recs.values()), \
        "pool pressure never triggered an evict/requeue"
    # a requeued request restarts from scratch and still finishes in full
    for req in reqs:
        assert len(req.output_tokens) == req.params.max_new_tokens \
            or recs[req.request_id].kv_evicted
    pair = eng.pairs[0]
    assert pair.kv.pool.used == 0 and not pair.kv.seqs


def test_pool_exhaustion_truncate_knob(engine_factory, trace_factory):
    eng = engine_factory(kv_evict_policy="truncate", **TINY_POOL)
    eng.warmup()
    reqs = _pressure_trace(trace_factory)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    recs = eng.monitor.completed
    assert len(recs) == len(reqs)
    assert all(r.kv_requeued == 0 for r in recs)
    assert any(r.kv_evicted for r in recs), \
        "pool pressure never triggered a truncate-finish"


def test_paged_serves_context_beyond_max_len(engine_factory, trace_factory):
    """max_context extends per-sequence capacity past the dense per-slot
    max_len — a prompt longer than max_len serves end to end."""
    eng = engine_factory(max_context=192, **PAGED)
    eng.warmup()
    req = trace_factory("bursty", n=1, lo=120, hi=121, max_new=16)[0]
    assert len(req.prompt) > 96  # over the dense ceiling
    eng.submit(req)
    eng.run_until_done()
    assert len(req.output_tokens) == 16
    assert eng.monitor.completed[-1].generated == 16


def test_oversize_prompt_fails_terminally(engine_factory, trace_factory):
    eng = engine_factory(**PAGED)  # no max_context: ceiling = max_len = 96
    eng.warmup()
    req = trace_factory("bursty", n=1, lo=120, hi=121)[0]
    eng.submit(req)
    eng.run_until_done()
    assert req.error == "exceeds_max_context"
    assert eng.monitor.completed[-1].request_id == req.request_id


# ---------------------------------------------------------------------------
# KV manager serve mode (plain pytest — no hypothesis dependency)
# ---------------------------------------------------------------------------


def test_incremental_hash_matches_batch_rehash():
    mgr = KVCacheManager(64, block_size=4, serve_prefixes=True)
    prompt = list(range(10))
    mgr.allocate_sequence("r", prompt, extra_tokens=4)
    stream = list(prompt)
    alloc = mgr.seqs["r"]
    for step in ([7, 7], [3], [9, 1, 4], [2, 2, 2, 2]):
        granted = mgr.extend_up_to("r", len(step), tokens=step)
        assert granted == len(step)
        stream.extend(step)
    want = chain_hashes(stream, 4)
    assert alloc.n_hashed == len(want) * 4
    assert alloc.last_hash == want[-1]
    # every hashed generated block is registered for later prefix matches
    assert mgr.match_prefix(stream + [99]) == len(want) * 4


def test_serve_mode_shares_leading_run_only():
    mgr = KVCacheManager(64, block_size=4, serve_prefixes=True)
    a = mgr.allocate_sequence("a", list(range(12)))
    assert a.shared_blocks == 0
    # identical prompt: full blocks resident, but the cap leaves >= 1 token
    # to recompute (admission needs a last-token logit)
    b = mgr.allocate_sequence("b", list(range(12)))
    assert b.shared_blocks == 2
    assert b.block_ids[:2] == a.block_ids[:2]
    assert b.block_ids[2] != a.block_ids[2]
    # diverging prompt shares only the common leading run
    c = mgr.allocate_sequence("c", [*range(8), 99, 98, 97, 96])
    assert c.shared_blocks == 2
    assert c.block_ids[:2] == a.block_ids[:2]


def test_freed_pages_resurrect_until_recycled():
    mgr = KVCacheManager(8, block_size=4, serve_prefixes=True)
    a = mgr.allocate_sequence("a", list(range(12)))
    first_two = a.block_ids[:2]
    mgr.free_sequence("a")
    assert mgr.pool.used == 0
    assert mgr.match_prefix(list(range(12))) == 8  # still resident
    b = mgr.allocate_sequence("b", list(range(12)))
    assert b.block_ids[:2] == first_two and b.shared_blocks == 2
    mgr.free_sequence("b")
    # churn through the pool so the free list recycles the cached pages
    for i in range(2):
        mgr.allocate_sequence(f"x{i}", [100 + i] * 16)
    assert mgr.match_prefix(list(range(12))) == 0
    for i in range(2):
        mgr.free_sequence(f"x{i}")
    assert mgr.pool.used == 0


def test_max_seq_blocks_caps_allocation_and_margin():
    mgr = KVCacheManager(64, block_size=4, serve_prefixes=True, max_seq_blocks=3)
    assert mgr.allocate_sequence("big", list(range(13))) is None  # 4 blocks
    assert mgr.allocate_sequence("ok", list(range(8))) is not None
    assert mgr.extend_up_to("ok", 8) == 4  # one more block, then the ceiling
    assert mgr.ensure_margin("ok", 4) == ("ceiling", 0)


# ---------------------------------------------------------------------------
# routing + cost model units
# ---------------------------------------------------------------------------


def test_flowguard_prefix_term_breaks_tie():
    from repro.core.flowguard import FlowGuard, FlowGuardConfig
    from repro.core.metrics import WorkerMetrics

    now = 100.0
    metrics = {
        i: WorkerMetrics(worker_id=i, timestamp=now) for i in (0, 1)
    }
    fg = FlowGuard()
    base, _ = fg.select(metrics, now)
    assert base == 0  # tie-break prefers the lowest id
    steered, scores = fg.select(metrics, now, prefix_scores={1: 0.8})
    assert steered == 1
    assert scores[1] == pytest.approx(scores[0] + 0.3 * 0.8)
    # weight off => term gone
    fg0 = FlowGuard(FlowGuardConfig(prefix_weight=0.0))
    again, _ = fg0.select(metrics, now, prefix_scores={1: 0.8})
    assert again == 0
    with pytest.raises(ValueError):
        FlowGuardConfig(prefix_weight=-0.1)


def test_saved_ticks_chunked_quantisation():
    from repro.configs import reduced_config
    from repro.serving.cost_model import PrefillDelayEstimator

    cfg = dataclasses.replace(reduced_config("qwen3-1.7b"), n_layers=2)
    est = PrefillDelayEstimator(cfg, prefill_chunk=16)
    assert est.saved_ticks(64, 48) == 3.0  # 4 chunks -> 1 chunk
    assert est.saved_frac(64, 48) == pytest.approx(0.75)
    assert est.saved_frac(64, 0) == 0.0
    est2 = PrefillDelayEstimator(cfg)
    assert 0.0 < est2.saved_frac(64, 48) <= 1.0
    assert est2.saved_frac(0, 0) == 0.0


def test_serve_config_paged_roundtrip_and_validation():
    from repro.api.config import ServeConfig

    cfg = ServeConfig.reduced_smoke(
        paged_kv=True, kv_block_size=16, max_len=96, max_context=192,
        max_new_tokens=12,
    )
    econf = cfg.build_engine_config()
    assert econf.paged_kv and econf.max_context == 192
    assert econf.kv_evict_policy == "requeue"
    assert ServeConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError):
        cfg.replace(kv_evict_policy="drop")
    with pytest.raises(ValueError):
        cfg.replace(max_context=64)  # below max_len
    with pytest.raises(ValueError):
        cfg.replace(max_len=90)  # not a multiple of kv_block_size
    with pytest.raises(ValueError):
        cfg.replace(draft="model")  # draft lane keeps a dense cache


def test_frontend_ceiling_is_max_context_when_paged():
    """StreamServe.submit admits prompts past max_len when paged
    max_context raises the ceiling, and rejects past max_context —
    without this the engine-level long-context path is unreachable
    through the public API.  (Engine construction stubbed: the guard
    runs before any engine call.)"""
    from repro.api.config import ServeConfig
    from repro.api.frontend import StreamServe
    from repro.serving.request import SamplingParams

    class _EngineStub:
        submitted = None

        def submit(self, req):
            self.submitted = req

    serve = StreamServe.__new__(StreamServe)
    serve.config = ServeConfig.reduced_smoke(
        paged_kv=True, kv_block_size=16, max_len=96, max_context=192)
    serve.engine = _EngineStub()
    # past max_len but under max_context: admitted in paged mode
    serve.submit(list(range(120)), SamplingParams(max_new_tokens=8))
    assert serve.engine.submitted is not None
    assert len(serve.engine.submitted.prompt) == 120
    with pytest.raises(ValueError, match="exceeds max_context"):
        serve.submit(list(range(200)), SamplingParams(max_new_tokens=8))
    # dense config: the legacy max_len guard (and message) is unchanged
    serve.config = ServeConfig.reduced_smoke(max_len=96)
    with pytest.raises(ValueError, match="exceeds max_len"):
        serve.submit(list(range(120)), SamplingParams(max_new_tokens=8))
