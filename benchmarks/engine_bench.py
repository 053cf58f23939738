"""Engine microbenchmark — the hot-path perf trajectory (BENCH_engine.json).

Drives ``PipeServeEngine`` (real JAX execution) over the paper's four
workload suites (alpaca / gsm8k / humaneval / sum) plus the mixed
multi-tenant trace, and records per trace:

* ``tokens_per_s``        — generated tokens / serve-phase wall time
* ``p50_step_ms``/``p99_step_ms`` — engine-step latency distribution
* ``admission_p50_ms``    — submit -> first-token wall latency
* ``retraces_steady``     — jit cache-size growth during serving (must be 0
  after ``engine.warmup()``: the shape-bucketing contract)

A second, bucketing-off engine (``prefill_buckets=False``,
``verify_buckets=None`` — the pre-bucketing hot path that re-traces XLA per
distinct prompt length and speculation depth) replays the mixed trace for
``speedup_mixed``.

SLO control plane: the mixed trace is replayed with alternating tight /
relaxed per-request SLO targets (the mixed-SLO trace) on the full control
plane (per-row speculation depths + SLO routing) and on a single-depth /
FIFO baseline engine; the ``slo`` block records TTFT/TPOT attainment for
both plus the mean speculation depth per SLO class (tick-time metrics).

Chunked prefill: a long-prompt trace (one near-max prompt followed by short
deadline-carrying requests) is served with ``prefill_chunk`` on, preemption
on vs off.  The ``chunked`` block records the compiled prefill trace count
(the contract: exactly ONE regardless of prompt length) and the short
requests' tick-time TTFT p99 under both scheduling modes — preemption must
let the shorts jump the long prompt's chunks.

StreamTrace observability: the mixed trace is replayed on two fresh engines
(``trace="on"`` vs ``trace="off"``, best-of-N each); the ``obs`` block
records the tokens/s overhead fraction (contract: < 5%), retrace count
(contract: 0), Chrome-trace span counts per worker lane
(BENCH_obs_trace.json artifact) and Prometheus histogram presence.

  PYTHONPATH=src python benchmarks/engine_bench.py               # standard
  PYTHONPATH=src python benchmarks/engine_bench.py --reduced     # CI smoke
  PYTHONPATH=src python benchmarks/engine_bench.py --fail-on-retrace

Output: BENCH_engine.json at the repo root (override with --out).  Every PR
appends a point to this trajectory; CI fails the smoke job on any
steady-state retrace.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

SUITES = ("alpaca", "gsm8k", "humaneval", "sum")


def _percentile(vals: List[float], p: float) -> float:
    if not vals:
        return 0.0
    vals = sorted(vals)
    # nearest-rank: ceil(p/100 * n) - 1, matching PerformanceMonitor.summary()
    return vals[max(math.ceil(p / 100.0 * len(vals)) - 1, 0)]


def _clip_prompts(reqs, max_prompt: int):
    for sim in reqs:
        sim.request.prompt = list(sim.request.prompt)[:max_prompt]
    return [sim.request for sim in reqs]


# tick-unit SLO classes for the mixed-SLO trace: tight rows must see their
# first token within 3 engine ticks and sustain >= 1 token/tick; relaxed rows
# only need eventual service.  Alternating assignment keeps the trace
# adversarial (every queue wave holds both classes).
SLO_TIGHT = (3.0, 1.0)     # (slo_ttft, slo_tpot)
SLO_RELAXED = (50.0, 4.0)


def attach_slos(reqs):
    for i, r in enumerate(reqs):
        r.slo_ttft, r.slo_tpot = SLO_TIGHT if i % 2 == 0 else SLO_RELAXED
        # deadlines are relative to arrival; let the scheduler stamp the
        # submission tick (the serving engine's clock has been running)
        r.arrival_time = None
    return reqs


def slo_attainment(reqs) -> Dict[str, float]:
    """TTFT/TPOT attainment + mean depth per SLO class (engine-tick time).

    Each target is judged over the requests that carry it (partial-SLO
    requests are legal); shed requests miss every target they carry.
    """
    ttft_ok = ttft_n = tpot_ok = tpot_n = n = 0
    depth: Dict[str, List[float]] = {"tight": [], "relaxed": []}
    for r in reqs:
        if r.slo_ttft is None and r.slo_tpot is None:
            continue
        n += 1
        arrived = r.arrival_time or 0.0
        infeasible = r.error == "slo_infeasible"
        if r.slo_ttft is not None:
            ttft_n += 1
            if not infeasible and r.token_times and (
                r.token_times[0] - arrived
            ) <= r.slo_ttft:
                ttft_ok += 1
        if r.slo_tpot is not None:
            tpot_n += 1
            measured = r.measured_tpot()
            # <2 distinct token times: trivially attained
            if not infeasible and (measured is None or measured <= r.slo_tpot):
                tpot_ok += 1
        cls = "tight" if (r.slo_ttft, r.slo_tpot) == SLO_TIGHT else "relaxed"
        if r.spec_depths:
            depth[cls].append(sum(r.spec_depths) / len(r.spec_depths))
    mean = lambda xs: round(sum(xs) / len(xs), 2) if xs else 0.0  # noqa: E731
    return {
        "requests": n,
        "ttft_attainment": round(ttft_ok / max(ttft_n, 1), 3),
        "tpot_attainment": round(tpot_ok / max(tpot_n, 1), 3),
        "shed": sum(1 for r in reqs if r.error == "slo_infeasible"),
        "mean_depth_tight": mean(depth["tight"]),
        "mean_depth_relaxed": mean(depth["relaxed"]),
    }


def long_prompt_trace(vocab_size: int, max_prompt: int, max_new: int,
                      n_short: int = 3):
    # n_short stays below the decode-slot count so the TTFT tail measures
    # prefill interference, not decode-slot contention
    """One near-max prompt plus short deadline-carrying requests — the
    adversarial prefill-interference trace.  The shorts arrive AFTER the
    long prompt has started prefilling (``serve_staged``): without chunked
    preemption every one of them waits for the whole long prefill."""
    import numpy as np

    from repro.serving.request import Request, SamplingParams

    rng = np.random.default_rng(17)
    long = Request(prompt=rng.integers(0, vocab_size, max_prompt).tolist(),
                   params=SamplingParams(max_new_tokens=max_new))
    shorts = [
        Request(prompt=rng.integers(0, vocab_size, 12).tolist(),
                params=SamplingParams(max_new_tokens=max_new),
                slo_ttft=60.0)  # earlier deadline than the long (best-effort)
        for _ in range(n_short)
    ]
    return long, shorts


def serve_staged(engine, long, shorts, max_steps: int = 2000) -> Dict[str, float]:
    """Submit the long prompt, let it start prefilling for one tick, then
    land the shorts mid-prefill and drain (tick-time metrics)."""
    cache_before = engine.jit_cache_total()
    engine.submit(long)
    engine.step()
    for r in shorts:
        engine.submit(r)
    steps = 1
    while not engine.drained() and steps < max_steps:
        engine.step()
        steps += 1
    return {
        "steps": steps,
        "retraces_steady": engine.jit_cache_total() - cache_before,
    }


def ttft_ticks(reqs) -> List[float]:
    """Tick-time TTFT per request (deterministic, unlike wall-clock)."""
    return [
        r.token_times[0] - (r.arrival_time or 0.0)
        for r in reqs if r.token_times
    ]


def serve_trace(engine, reqs, max_steps: int = 20_000) -> Dict[str, float]:
    """Submit a whole trace, drive the engine dry, measure wall-clock."""
    cache_before = engine.jit_cache_total()
    t_submit = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    step_ms: List[float] = []
    first_tok_ms: Dict[str, float] = {}
    for _ in range(max_steps):
        if engine.drained():
            break
        t0 = time.perf_counter()
        engine.step()
        step_ms.append((time.perf_counter() - t0) * 1e3)
        now_ms = (time.perf_counter() - t_submit) * 1e3
        for r in reqs:
            if r.output_tokens and r.request_id not in first_tok_ms:
                first_tok_ms[r.request_id] = now_ms
    wall = time.perf_counter() - t_submit
    generated = sum(len(r.output_tokens) for r in reqs)
    admits = list(first_tok_ms.values())
    return {
        "requests": len(reqs),
        "generated_tokens": generated,
        "serve_wall_s": round(wall, 3),
        "tokens_per_s": round(generated / max(wall, 1e-9), 2),
        "steps": len(step_ms),
        "p50_step_ms": round(_percentile(step_ms, 50), 2),
        "p99_step_ms": round(_percentile(step_ms, 99), 2),
        "admission_p50_ms": round(_percentile(admits, 50), 2),
        "admission_p99_ms": round(_percentile(admits, 99), 2),
        "retraces_steady": engine.jit_cache_total() - cache_before,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reduced", action="store_true", help="CI-sized smoke run")
    ap.add_argument("--out", default=str(ROOT / "BENCH_engine.json"))
    ap.add_argument("--fail-on-retrace", action="store_true",
                    help="exit 1 if any bucketed run retraced in steady state")
    ap.add_argument("--skip-legacy", action="store_true",
                    help="skip the bucketing-off baseline replay")
    args = ap.parse_args(argv)

    import jax

    from repro.configs import reduced_config
    from repro.core.engine import EngineConfig, PipeServeEngine
    from repro.data.workloads import sample_mixed, sample_requests
    from repro.distributed.sharding import unzip_params
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    enable_compile_cache()

    n_suite = 4 if args.reduced else 12
    n_mixed = 2 if args.reduced else 5          # per suite -> 8 / 20 requests
    max_new = 8 if args.reduced else 16
    max_len = 192
    max_prompt = max_len - max_new - 8

    cfg = dataclasses.replace(reduced_config("qwen3-1.7b"), n_layers=2)
    model = build_model(cfg)
    params, _ = unzip_params(model.init(jax.random.PRNGKey(0)))
    base = {"max_batch": 4, "max_len": max_len, "kv_blocks": 4096,
            "kv_block_size": 16}

    def trace(name: str):
        if name == "mixed":
            sims = sample_mixed(n_mixed, vocab_size=cfg.vocab_size)
            for s in sims:
                s.request.params.max_new_tokens = max_new
        else:
            sims = sample_requests(
                name, n_suite, vocab_size=cfg.vocab_size, max_new_override=max_new
            )
        return _clip_prompts(sims, max_prompt)

    # ---- bucketed engine: warm once, then serve every suite ----------------
    print(f"engine_bench: building bucketed engine ({cfg.name}, reduced model)")
    engine = PipeServeEngine(cfg, params, n_pairs=1, econf=EngineConfig(**base))
    t0 = time.perf_counter()
    n_programs = engine.warmup(max_prompt_len=max_prompt)
    warmup_s = time.perf_counter() - t0
    print(f"  warmup: {n_programs} programs in {warmup_s:.1f}s")

    results: Dict[str, Dict[str, float]] = {}
    for name in SUITES + ("mixed",):
        results[name] = serve_trace(engine, trace(name))
        r = results[name]
        print(f"  {name:10s} {r['tokens_per_s']:8.1f} tok/s  "
              f"p50 {r['p50_step_ms']:6.1f}ms  p99 {r['p99_step_ms']:6.1f}ms  "
              f"retraces {r['retraces_steady']}")

    # ---- SLO control plane on the mixed-SLO trace --------------------------
    # full plane (per-row depths + SLO routing, the default) vs a
    # single-depth / FIFO engine; both warmed, both retrace-free
    print("engine_bench: mixed-SLO trace (per-row depths + SLO routing)")
    slo_reqs = attach_slos(trace("mixed"))
    results["mixed_slo"] = serve_trace(engine, slo_reqs)
    slo_full = slo_attainment(slo_reqs)
    print(f"  slo        ttft {slo_full['ttft_attainment']:.0%}  "
          f"tpot {slo_full['tpot_attainment']:.0%}  "
          f"depth tight/relaxed {slo_full['mean_depth_tight']}/"
          f"{slo_full['mean_depth_relaxed']}")
    single_engine = PipeServeEngine(
        cfg, params, n_pairs=1,
        econf=EngineConfig(per_row_depth=False, slo_routing=False, **base),
    )
    single_engine.warmup(max_prompt_len=max_prompt)
    slo_base_reqs = attach_slos(trace("mixed"))
    results["mixed_slo_baseline"] = serve_trace(single_engine, slo_base_reqs)
    slo_base = slo_attainment(slo_base_reqs)
    print(f"  slo-base   ttft {slo_base['ttft_attainment']:.0%}  "
          f"tpot {slo_base['tpot_attainment']:.0%}")

    # ---- chunked prefill on the long-prompt trace (preemption on vs off) ---
    print("engine_bench: chunked prefill, long-prompt trace (preempt on/off)")
    chunk = 48
    chunked: Dict[str, Any] = {"trace": "long_prompt", "prefill_chunk": chunk}
    for label, preempt in (("preempt_on", True), ("preempt_off", False)):
        ceng = PipeServeEngine(
            cfg, params, n_pairs=1,
            econf=EngineConfig(prefill_chunk=chunk, prefill_preempt=preempt,
                               **base),
        )
        ceng.warmup(max_prompt_len=max_prompt)
        long_req, short_reqs = long_prompt_trace(cfg.vocab_size, max_prompt, max_new)
        results[f"chunked_{label}"] = serve_staged(ceng, long_req, short_reqs)
        shorts = _percentile(ttft_ticks(short_reqs), 99)
        longs = ttft_ticks([long_req])
        chunked[f"short_ttft_p99_ticks_{label}"] = shorts
        chunked[f"long_ttft_ticks_{label}"] = longs[0] if longs else None
        if preempt:
            # the chunked contract: ONE compiled prefill program total
            chunked["prefill_traces"] = ceng.jit_cache_sizes()["chunk_prefill"]
        print(f"  {label:12s} short TTFT p99 {shorts:5.1f} ticks  "
              f"long TTFT {chunked[f'long_ttft_ticks_{label}']}  "
              f"retraces {results[f'chunked_{label}']['retraces_steady']}")

    # ---- paged KV + radix prefix reuse -------------------------------------
    # the mixed trace twice through one paged engine: wave 2 re-submits the
    # exact prompts, so its prefill work rides the radix-resident pages; a
    # final long-context request proves service beyond the dense per-slot
    # max_len ceiling (pages, not slots, bound the context)
    print("engine_bench: paged KV (radix prefix reuse + long context)")
    import numpy as np

    from repro.serving.request import Request, SamplingParams

    paged_max_context = 256
    peng = PipeServeEngine(
        cfg, params, n_pairs=1,
        econf=EngineConfig(paged_kv=True, max_context=paged_max_context, **base),
    )
    peng.warmup()  # uncapped: covers the long-context buckets too
    wave1, wave2 = trace("mixed"), trace("mixed")
    results["paged_cold"] = serve_trace(peng, wave1)
    results["paged_warm"] = serve_trace(peng, wave2)
    hit_tokens = sum(r.cache_hit_tokens for r in wave2)
    prompt_tokens = sum(len(r.prompt) for r in wave2)
    long_prompt_len = paged_max_context - max_new - 16
    long_ctx = Request(
        prompt=np.random.default_rng(19).integers(
            0, cfg.vocab_size, long_prompt_len
        ).tolist(),
        params=SamplingParams(max_new_tokens=max_new),
    )
    results["paged_long_context"] = serve_trace(peng, [long_ctx])
    paged = {
        "trace": "mixed x2 + long_context",
        "max_context": paged_max_context,
        "dense_max_len": base["max_len"],
        "prefix_hit_rate": round(hit_tokens / max(prompt_tokens, 1), 3),
        "tokens_per_s": results["paged_warm"]["tokens_per_s"],
        "cold_tokens_per_s": results["paged_cold"]["tokens_per_s"],
        "dense_tokens_per_s": results["mixed"]["tokens_per_s"],
        "max_context_served": len(long_ctx.prompt) + len(long_ctx.output_tokens),
        "retraces_steady": (
            results["paged_cold"]["retraces_steady"]
            + results["paged_warm"]["retraces_steady"]
            + results["paged_long_context"]["retraces_steady"]
        ),
    }
    print(f"  prefix hit rate {paged['prefix_hit_rate']:.0%}  "
          f"warm {paged['tokens_per_s']:.1f} tok/s vs dense "
          f"{paged['dense_tokens_per_s']:.1f}  "
          f"context served {paged['max_context_served']} "
          f"(dense ceiling {base['max_len']})  "
          f"retraces {paged['retraces_steady']}")

    # ---- bucketing-off baseline (pre-PR hot path) on the mixed trace -------
    legacy = None
    if not args.skip_legacy:
        print("engine_bench: replaying mixed trace on the bucketing-off baseline")
        legacy_engine = PipeServeEngine(
            cfg, params, n_pairs=1,
            econf=EngineConfig(prefill_buckets=False, verify_buckets=None, **base),
        )
        legacy = serve_trace(legacy_engine, trace("mixed"))
        print(f"  legacy     {legacy['tokens_per_s']:8.1f} tok/s  "
              f"retraces {legacy['retraces_steady']}")

    # ---- StreamTrace observability overhead (trace=on vs trace=off) --------
    # the mixed trace A/B on two fresh warmed engines; best-of-N wall-clock
    # per side denoises CI jitter.  The contract: tracing costs < 5% tokens/s
    # and adds zero steady-state retraces (payloads are host values the
    # engine already fetched).
    print("engine_bench: StreamTrace overhead (trace=on vs trace=off)")
    obs_repeats = 5
    obs_engines = {}
    obs_best: Dict[str, float] = {"off": 0.0, "on": 0.0}
    obs_retraces = 0
    for mode in ("off", "on"):
        oeng = PipeServeEngine(
            cfg, params, n_pairs=1,
            econf=EngineConfig(trace=mode, **base),
        )
        oeng.warmup(max_prompt_len=max_prompt)
        obs_engines[mode] = oeng

    def obs_trace():
        # 3x the mixed trace: the reduced run is otherwise so short
        # (~150 ms) that scheduler jitter swamps the tracing cost
        sims = sample_mixed(n_mixed * 3, vocab_size=cfg.vocab_size)
        for s in sims:
            s.request.params.max_new_tokens = max_new
        return _clip_prompts(sims, max_prompt)

    # interleave the sides so machine-level drift (turbo, page cache, GC)
    # hits both equally; best-of-N per side then denoises the remainder
    for _ in range(obs_repeats):
        for mode in ("off", "on"):
            r = serve_trace(obs_engines[mode], obs_trace())
            obs_best[mode] = max(obs_best[mode], r["tokens_per_s"])
            obs_retraces += r["retraces_steady"]
    overhead = max(0.0, 1.0 - obs_best["on"] / max(obs_best["off"], 1e-9))
    oeng = obs_engines["on"]
    obs_trace_path = str(Path(args.out).parent / "BENCH_obs_trace.json")
    doc = oeng.export_chrome_trace(obs_trace_path)
    span_counts: Dict[str, int] = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            lane = ("prefill", "decode", "verify")[ev["tid"]]
            key = f"pair{ev['pid']}.{lane}"
            span_counts[key] = span_counts.get(key, 0) + 1
    prom = oeng.prometheus_text()
    obs = {
        "trace": "mixed",
        "repeats": obs_repeats,
        "tokens_per_s_off": obs_best["off"],
        "tokens_per_s_on": obs_best["on"],
        "overhead_frac": round(overhead, 4),
        "retraces_steady": obs_retraces,
        "events_retained": len(oeng.trace_events()),
        "chrome_trace": obs_trace_path,
        "chrome_spans": span_counts,
        "prom_has_ttft_histogram": "streamserve_ttft_ticks_bucket" in prom,
        "prom_has_tpot_histogram": "streamserve_tpot_ticks_bucket" in prom,
    }
    print(f"  off {obs_best['off']:.1f} tok/s  on {obs_best['on']:.1f} tok/s  "
          f"overhead {overhead:.1%}  retraces {obs_retraces}  "
          f"spans {sum(span_counts.values())}")

    retraces = max(r["retraces_steady"] for r in results.values())
    retraces = max(retraces, obs_retraces)
    out = {
        "bench": "engine",
        "mode": "reduced" if args.reduced else "standard",
        "arch": cfg.name,
        "config": {"n_layers": cfg.n_layers, "max_new_tokens": max_new, **base},
        "warmup": {"programs": n_programs, "wall_s": round(warmup_s, 2)},
        "workloads": results,
        "slo": {
            "trace": "mixed_slo",
            "tight": {"slo_ttft": SLO_TIGHT[0], "slo_tpot": SLO_TIGHT[1]},
            "relaxed": {"slo_ttft": SLO_RELAXED[0], "slo_tpot": SLO_RELAXED[1]},
            **slo_full,
            "baseline_ttft_attainment": slo_base["ttft_attainment"],
            "baseline_tpot_attainment": slo_base["tpot_attainment"],
            "baseline_shed": slo_base["shed"],
        },
        "chunked": chunked,
        "paged": paged,
        "obs": obs,
        "legacy_mixed": legacy,
        "speedup_mixed": (
            round(results["mixed"]["tokens_per_s"] / legacy["tokens_per_s"], 2)
            if legacy else None
        ),
        "steady_state_retraces": retraces,
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"engine_bench: wrote {args.out}")
    if out["speedup_mixed"] is not None:
        print(f"  mixed-trace speedup vs pre-bucketing path: {out['speedup_mixed']}x")
    if args.fail_on_retrace and retraces > 0:
        print(f"FAIL: {retraces} steady-state retraces (expected 0)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
