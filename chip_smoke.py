"""Smoke run of the serving path on one TPU chip, at qwen3-1.7b's published
width.  It proves the system starts and serves correctly on the chip; its
times are set-up and wall times of a smoke run, not a benchmark.

    python chip_smoke.py     # needs a TPU; exits non-zero anywhere else

Phases, all in this one process (a chip belongs to one process at a time):

1. kernels — the dense and paged Pallas decode kernels and the prefill flash
   kernel at full width, against the jnp references in ``kernels/ref.py``
   (run at highest matmul precision) within ``ATOL``;
2. dense — ``ServeConfig.paper_stream_pairs("qwen3-1.7b")`` (2 stream
   pairs, 16 slots, 2048-token dense KV per pair) serves 8 seeded requests
   of three prompt lengths, two of them sharing a prefix, through
   ``StreamServe.submit`` / ``RequestHandle.stream``, with the n-gram draft
   and SpecuStream on so the verify buckets run;
3. paged — the same traffic with ``paged_kv=True`` and ``PAGES`` pages of
   16 tokens per pair, after the dense engine has been released.

Each serving phase runs twice on fresh engines and must give identical
tokens; every request must finish with all its tokens, no record may be
failed, evicted or requeued, and each compiled prefill, decode and verify
program must hold a Pallas kernel (``tpu_custom_call``).  Any failed check
exits non-zero.  The last line of standard output is one JSON object naming
the device.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.api import ServeConfig, StreamServe  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.distributed.sharding import unzip_params  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import (  # noqa: E402
    decode_attention_paged_pallas,
    decode_attention_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.request import RequestState  # noqa: E402

ARCH = "qwen3-1.7b"
SEED = 0
N_REQUESTS = 8
PROMPT_LENS = (40, 72, 120)
SHARED_PREFIX = 32         # tokens the last prompt shares with the first
MAX_NEW = 32
PAGES = 1024               # per pair: 16384 tokens, 1.75 GiB of bf16 KV
# bf16 kernel outputs of magnitude <= 1 against an f32 reference: 4 bf16
# ulps at 1.0 (rounding of the output plus a different summation order)
ATOL = 2.0 ** -5


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


# ------------------------------------------------------------------ kernels
def kernel_checks(cfg) -> None:
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, S, ps = 16, 2048, 16
    rng = np.random.default_rng(SEED)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def err(got, want):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))

    def highest(fn, *a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)

    k, v = rand(B, S, K, D), rand(B, S, K, D)
    # paged layout of the same rows: shuffled head-major pages
    P = S // ps
    perm = rng.permutation(B * P).reshape(B, P)
    n_pages = B * P
    k_pool = jnp.zeros((n_pages, K, ps, D), jnp.bfloat16)
    v_pool = jnp.zeros((n_pages, K, ps, D), jnp.bfloat16)
    k_pool = k_pool.at[perm.reshape(-1)].set(
        k.reshape(B * P, ps, K, D).swapaxes(1, 2))
    v_pool = v_pool.at[perm.reshape(-1)].set(
        v.reshape(B * P, ps, K, D).swapaxes(1, 2))
    for T in (1, 9):
        q = rand(B, T, H, D)
        clen = jnp.asarray(rng.integers(T + 1, S + 1, size=B), jnp.int32)
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        kv_pos = jnp.where(pos < clen[:, None], pos, -1)
        want = highest(ref.decode_attention, q, k, v, clen, kv_positions=kv_pos)
        e = err(decode_attention_pallas(q, k, v, clen, kv_positions=kv_pos), want)
        print(f"smoke kernel decode_attention T={T}: max_abs_err={e} (limit {ATOL})")
        check(e <= ATOL, f"decode_attention T={T} error {e} > {ATOL}")
        live = (np.arange(P)[None] * ps) < np.asarray(clen)[:, None]
        bt = jnp.asarray(np.where(live, perm, -1), jnp.int32)
        want = highest(ref.decode_attention_paged, q, k_pool, v_pool, clen, bt)
        e = err(decode_attention_paged_pallas(q, k_pool, v_pool, clen, bt), want)
        print(f"smoke kernel decode_attention_paged T={T}: max_abs_err={e} (limit {ATOL})")
        check(e <= ATOL, f"decode_attention_paged T={T} error {e} > {ATOL}")
    Bp, Sq = 4, 512
    q, kf, vf = rand(Bp, Sq, H, D), rand(Bp, Sq, K, D), rand(Bp, Sq, K, D)
    e = err(flash_attention_pallas(q, kf, vf), highest(ref.flash_attention, q, kf, vf))
    print(f"smoke kernel flash_attention Sq={Sq}: max_abs_err={e} (limit {ATOL})")
    check(e <= ATOL, f"flash_attention error {e} > {ATOL}")


# ------------------------------------------------------------------ serving
def traffic(vocab: int):
    rng = np.random.default_rng(SEED)
    lens = rng.choice(PROMPT_LENS, size=N_REQUESTS)
    lens[0] = lens[-1] = max(PROMPT_LENS)
    prompts = [rng.integers(0, vocab, int(n)).tolist() for n in lens]
    prompts[-1][:SHARED_PREFIX] = prompts[0][:SHARED_PREFIX]
    return prompts


def program_kernels(serve: StreamServe, max_prompt: int) -> dict:
    """``tpu_custom_call`` counts in the compiled prefill, decode and verify
    programs of pair 0's lane, lowered with the lane's own arguments."""
    pair = serve.engine.pairs[0]
    lane = pair.lane
    B = lane.max_batch
    S = pair._bucket(max_prompt, pair._len_buckets)
    zeros_b = jnp.zeros((B,), jnp.int32)
    if serve.config.paged_kv:
        prefill = eng._paged_admit_step.lower(
            lane.model.chunk_prefill, lane.params, lane.cache, lane.cache["bt"],
            jnp.zeros((B, S), jnp.int32), zeros_b, zeros_b,
        )
    else:
        Bb = pair._admit_buckets[-1]
        prefill = eng._lane_prefill.lower(
            lane.model.prefill, lane.params, lane.max_len,
            {"tokens": jnp.zeros((Bb, S), jnp.int32),
             "lengths": jnp.full((Bb,), S, jnp.int32)},
        )
    T_verify = serve.config.verify_buckets[-1] + 1
    programs = {
        "prefill": prefill,
        "decode": eng._lane_decode.lower(
            lane.model.decode_step, lane.params, lane.cache,
            jnp.zeros((B, 1), jnp.int32)),
        f"verify_T{T_verify}": eng._lane_decode.lower(
            lane.model.decode_step, lane.params, lane.cache,
            jnp.zeros((B, T_verify), jnp.int32)),
    }
    return {name: low.compile().as_text().count("tpu_custom_call")
            for name, low in programs.items()}


def serve_once(config: ServeConfig, params, prompts, dev) -> dict:
    t0 = time.perf_counter()
    serve = StreamServe(config, params=params)
    max_prompt = max(len(p) for p in prompts)
    n_programs = serve.engine.warmup(max_prompt_len=max_prompt)
    kernels = program_kernels(serve, max_prompt)
    setup_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    handles = [serve.submit(p) for p in prompts]
    tokens = [h.result() for h in handles]
    wall_s = time.perf_counter() - t1

    states = [h.state for h in handles]
    records = list(serve.monitor.completed)
    return {
        "setup_s": setup_s, "wall_s": wall_s, "programs": n_programs,
        "kernels": kernels, "tokens": tokens, "states": states,
        "records": records, "peak_bytes_in_use": peak_bytes(dev),
        "cache_hit_tokens": sum(h.request.cache_hit_tokens for h in handles),
    }


def run_phase(name: str, config: ServeConfig, params, prompts, dev) -> None:
    runs = []
    for i in (1, 2):
        r = serve_once(config, params, prompts, dev)
        gc.collect()  # release this run's engine before the next one
        print(f"smoke {name} run {i}: setup_s={r['setup_s']} wall_s={r['wall_s']} "
              f"programs={r['programs']} kernels={r['kernels']} "
              f"tokens={sum(len(t) for t in r['tokens'])} "
              f"cache_hit_tokens={r['cache_hit_tokens']} "
              f"peak_bytes_in_use={r['peak_bytes_in_use']}")
        for prog, n in r["kernels"].items():
            check(n > 0, f"{name}: compiled {prog} program has no tpu_custom_call")
        check(all(s is RequestState.FINISHED for s in r["states"]),
              f"{name}: request states {[s.value for s in r['states']]}")
        check(all(len(t) == MAX_NEW for t in r["tokens"]),
              f"{name}: token counts {[len(t) for t in r['tokens']]}")
        recs = r["records"]
        check(len(recs) == len(prompts), f"{name}: {len(recs)} records")
        check(not any(x.kv_evicted or x.kv_requeued or x.cancelled for x in recs),
              f"{name}: a record was evicted, requeued or cancelled")
        runs.append(r["tokens"])
    check(runs[0] == runs[1], f"{name}: the two runs gave different tokens")


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} ({dev.device_kind})")
    print(f"smoke device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"bytes_limit={(dev.memory_stats() or {}).get('bytes_limit')} "
          f"compile cache {enable_compile_cache()}")

    base = ServeConfig.paper_stream_pairs(ARCH, max_new_tokens=MAX_NEW, seed=SEED)
    cfg = base.build_arch_config()
    t0 = time.perf_counter()
    kernel_checks(cfg)
    print(f"smoke kernels: {time.perf_counter() - t0}s")

    t0 = time.perf_counter()
    params, _ = unzip_params(jax.jit(build_model(cfg).init)(jax.random.PRNGKey(SEED)))
    jax.block_until_ready(params)
    print(f"smoke params: {sum(x.size for x in jax.tree.leaves(params))} "
          f"in {time.perf_counter() - t0}s")
    prompts = traffic(cfg.vocab_size)
    print(f"smoke traffic: {N_REQUESTS} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each")

    run_phase("dense", base, params, prompts, dev)
    run_phase("paged", base.replace(paged_kv=True, kv_blocks=PAGES),
              params, prompts, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
