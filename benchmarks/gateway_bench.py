"""Gateway load harness — open-loop HTTP traffic against the real gateway
(BENCH_gateway.json).

Unlike :mod:`engine_bench` (which drives ``engine.step()`` directly and
measures tick-time), this bench measures what a CLIENT sees: it starts the
full stack — ``StreamServe`` on the real JAX engine behind the asyncio
HTTP gateway — on a dedicated thread, then replays open-loop traffic over
real localhost sockets:

* **ramp stages**: Poisson arrivals (seeded ``random.Random`` expovariate
  gaps) at increasing offered QPS, plus a bursty stage where arrivals come
  in clumps — the arrival process never waits for responses (open loop),
  so queueing delay shows up in client-measured TTFT instead of being
  hidden by client-side backoff;
* **burst stage**: all clients connect at once (the ``--clients`` floor,
  default 64 concurrent SSE streams) — the saturation / backpressure probe.

Prompt mixes come from the existing workload suites
(:func:`repro.data.workloads.sample_mixed` — alpaca/gsm8k/humaneval/sum
interleaved), clipped to the gateway config's context budget.

Per stage the report records client-measured TTFT/TPOT p50/p99 (SSE frame
arrival stamps, ``perf_counter``), goodput (SLO-attaining completions/s),
completion + 429 rates, and peak concurrent streams.  The top-level block
records the saturation knee (first stage where the gateway sheds load or
p99 TTFT blows past the SLO), total 429s, and ``retraces_steady`` — jit
cache growth across all HTTP serving after warmup, which must stay 0.

  PYTHONPATH=src python benchmarks/gateway_bench.py              # standard
  PYTHONPATH=src python benchmarks/gateway_bench.py --reduced    # CI smoke

Output: BENCH_gateway.json at the repo root (override with --out).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import random
import sys
from pathlib import Path
from time import perf_counter
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

# wall-clock SLO targets for goodput accounting.  The reduced CPU model
# decodes a token in ~100ms-class steps with queueing on top, so the bounds
# are loose; they exist to make "goodput" a falsifiable number, not to
# mirror the paper's tick-time SLOs.
SLO_TTFT_S = 20.0
SLO_TPOT_S = 2.0


def _percentile(vals: List[float], p: float) -> float:
    if not vals:
        return 0.0
    vals = sorted(vals)
    # nearest-rank: ceil(p/100 * n) - 1, matching PerformanceMonitor.summary()
    return vals[max(math.ceil(p / 100.0 * len(vals)) - 1, 0)]


def _prompt_pool(cfg, vocab_size: int, n: int, seed: int) -> List[List[int]]:
    """Prompt mix from the paper's workload suites, clipped to the gateway
    config's KV budget (prompt + generation must fit max_len)."""
    from repro.data.workloads import sample_mixed

    sims = sample_mixed(max(n // 4 + 1, 8), seed=seed, vocab_size=vocab_size)
    cap = max(cfg.max_len - cfg.max_new_tokens - 1, 4)
    pool = [list(s.request.prompt)[:cap] for s in sims]
    rng = random.Random(seed ^ 0x5EED)
    rng.shuffle(pool)
    return pool[:n] if len(pool) >= n else [pool[i % len(pool)] for i in range(n)]


def _arrival_offsets(process: str, n: int, qps: float, rng: random.Random,
                     burst_size: int = 8) -> List[float]:
    """Open-loop arrival schedule (seconds from stage start).

    ``poisson``: exponential inter-arrival gaps at rate ``qps``.
    ``bursty``: clumps of ``burst_size`` simultaneous arrivals, clump gaps
    exponential at rate ``qps/burst_size`` — same offered load, maximally
    adversarial for admission/backpressure.
    """
    offsets: List[float] = []
    t = 0.0
    if process == "poisson":
        for _ in range(n):
            t += rng.expovariate(qps)
            offsets.append(t)
    elif process == "bursty":
        while len(offsets) < n:
            t += rng.expovariate(qps / burst_size)
            offsets.extend([t] * min(burst_size, n - len(offsets)))
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return offsets


class _Gauge:
    """Track live + peak concurrent streams (the >=64-clients evidence)."""

    def __init__(self) -> None:
        self.live = 0
        self.peak = 0

    def enter(self) -> None:
        self.live += 1
        self.peak = max(self.peak, self.live)

    def exit(self) -> None:
        self.live -= 1


async def _one_client(host: str, port: int, prompt: List[int], max_tokens: int,
                      delay: float, gauge: _Gauge) -> Dict[str, Any]:
    from repro.gateway.client import asse_collect, completion_body

    if delay > 0:
        await asyncio.sleep(delay)
    gauge.enter()
    try:
        return await asse_collect(
            host, port, "/v1/completions",
            completion_body(prompt, max_tokens, stream=True),
        )
    finally:
        gauge.exit()


def _stage_stats(results: List[Dict[str, Any]], wall: float,
                 max_tokens: int) -> Dict[str, Any]:
    """Client-side metrics for one stage: percentiles over per-request
    TTFT (submit -> first SSE token frame) and TPOT (mean gap between
    token frames), goodput = SLO-attaining completions / stage wall."""
    ttfts: List[float] = []
    tpots: List[float] = []
    completed = rejected = failed = good = 0
    for r in results:
        if r["status"] == 429:
            rejected += 1
            continue
        terminal = r["terminal"] or {}
        ok = (r["status"] == 200 and r["error"] is None
              and "usage" in terminal)
        if not ok:
            failed += 1
            continue
        completed += 1
        ttft = tpot = None
        if r["t_first"] is not None:
            ttft = r["t_first"] - r["t_submit"]
            ttfts.append(ttft)
        times = r["frame_times"]
        if len(times) >= 2:
            tpot = (times[-1] - times[0]) / (len(times) - 1)
            tpots.append(tpot)
        if (ttft is not None and ttft <= SLO_TTFT_S
                and (tpot is None or tpot <= SLO_TPOT_S)):
            good += 1
    n = len(results)
    return {
        "n_requests": n,
        "completed": completed,
        "rejected_429": rejected,
        "failed": failed,
        "completion_rate": completed / n if n else 0.0,
        "rate_429": rejected / n if n else 0.0,
        "ttft_p50_s": _percentile(ttfts, 50),
        "ttft_p99_s": _percentile(ttfts, 99),
        "tpot_p50_s": _percentile(tpots, 50),
        "tpot_p99_s": _percentile(tpots, 99),
        "throughput_rps": completed / wall if wall > 0 else 0.0,
        "goodput_rps": good / wall if wall > 0 else 0.0,
        "tokens_total": completed * max_tokens,
        "wall_s": wall,
    }


async def _run_stage(host: str, port: int, prompts: List[List[int]],
                     offsets: List[float], max_tokens: int,
                     gauge: _Gauge) -> List[Dict[str, Any]]:
    tasks = [
        asyncio.ensure_future(
            _one_client(host, port, prompts[i % len(prompts)], max_tokens,
                        offsets[i], gauge)
        )
        for i in range(len(offsets))
    ]
    return list(await asyncio.gather(*tasks))


def _find_knee(stages: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """First ramp stage where the gateway visibly saturates: it sheds load
    (429s), fails to complete the offered work, or p99 TTFT blows through
    the SLO bound.  None = the ramp never saturated (raise --qps)."""
    for st in stages:
        if (st["rate_429"] > 0.0 or st["completion_rate"] < 0.95
                or st["ttft_p99_s"] > SLO_TTFT_S):
            return {"qps": st["offered_qps"], "stage": st["name"],
                    "ttft_p99_s": st["ttft_p99_s"], "rate_429": st["rate_429"]}
    return None


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="CI smoke sizing (fewer/shorter requests)")
    ap.add_argument("--out", default=str(ROOT / "BENCH_gateway.json"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=64,
                    help="burst-stage concurrent SSE streams (floor 64)")
    ap.add_argument("--qps", type=float, default=None,
                    help="override the top ramp QPS")
    ap.add_argument("--requests-per-stage", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None,
                    help="tokens generated per request")
    args = ap.parse_args(argv)

    from repro.api import ServeConfig, StreamServe
    from repro.gateway import GatewayThread
    from repro.gateway.client import http_request
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    max_new = args.max_new or (4 if args.reduced else 8)
    per_stage = args.requests_per_stage or (24 if args.reduced else 80)
    clients = max(args.clients, 64)
    cfg = ServeConfig.reduced_smoke(
        max_new_tokens=max_new,
        gateway_port=0,                      # ephemeral: parallel CI safe
        gateway_max_pending=clients + 64,    # burst admits; headroom above
    )
    serve = StreamServe(cfg)
    print("warming up (pre-compiling shape buckets)...", flush=True)
    n_compiled = serve.engine.warmup()
    print(f"warmup compiled {n_compiled} traces", flush=True)

    gw = GatewayThread(serve, host=cfg.gateway_host, port=0,
                       max_pending=cfg.gateway_max_pending)
    host, port = gw.start()
    print(f"gateway up on {host}:{port}", flush=True)

    rng = random.Random(args.seed)
    prompts = _prompt_pool(cfg, serve.arch.vocab_size, per_stage * 4, args.seed)
    report: Dict[str, Any] = {
        "bench": "gateway",
        "config": {
            "arch": cfg.arch, "reduced": True, "n_pairs": cfg.n_pairs,
            "max_batch": cfg.max_batch, "max_new_tokens": max_new,
            "gateway_max_pending": cfg.gateway_max_pending,
            "slo_ttft_s": SLO_TTFT_S, "slo_tpot_s": SLO_TPOT_S,
            "seed": args.seed,
        },
        "stages": [],
    }

    jit_before = serve.engine.jit_cache_total()
    gauge = _Gauge()
    top_qps = args.qps or (8.0 if args.reduced else 24.0)
    ramp = [
        ("poisson", top_qps / 4),
        ("poisson", top_qps / 2),
        ("poisson", top_qps),
        ("bursty", top_qps),
    ]
    try:
        for process, qps in ramp:
            name = f"{process}@{qps:g}qps"
            offsets = _arrival_offsets(process, per_stage, qps, rng)
            rng.shuffle(prompts)
            t0 = perf_counter()
            results = asyncio.run(
                _run_stage(host, port, prompts, offsets, max_new, gauge))
            wall = perf_counter() - t0
            st = _stage_stats(results, wall, max_new)
            st.update({"name": name, "process": process, "offered_qps": qps})
            report["stages"].append(st)
            print(f"[{name}] completed={st['completed']}/{st['n_requests']} "
                  f"429={st['rejected_429']} ttft_p99={st['ttft_p99_s']:.2f}s "
                  f"tpot_p50={st['tpot_p50_s']:.3f}s "
                  f"goodput={st['goodput_rps']:.2f}rps", flush=True)

        # burst stage: every client connects at once — the concurrency and
        # backpressure probe (>=64 live SSE streams over real sockets)
        offsets = [0.0] * clients
        t0 = perf_counter()
        results = asyncio.run(
            _run_stage(host, port, prompts, offsets, max_new, gauge))
        wall = perf_counter() - t0
        burst = _stage_stats(results, wall, max_new)
        burst.update({"name": f"burst@{clients}", "process": "burst",
                      "offered_qps": clients / wall if wall > 0 else 0.0,
                      "clients": clients})
        report["burst"] = burst
        print(f"[burst@{clients}] completed={burst['completed']}/{clients} "
              f"429={burst['rejected_429']} peak_streams={gauge.peak} "
              f"ttft_p99={burst['ttft_p99_s']:.2f}s", flush=True)

        status, _, body = http_request(host, port, "GET", "/metrics")
        report["metrics_bytes"] = len(body) if status == 200 else 0
    finally:
        gw.stop()

    report["max_concurrent_streams"] = gauge.peak
    report["retraces_steady"] = serve.engine.jit_cache_total() - jit_before
    all_stages = report["stages"] + [report["burst"]]
    report["rejected_429_total"] = sum(s["rejected_429"] for s in all_stages)
    report["saturation"] = _find_knee(report["stages"]) or (
        {"qps": report["burst"]["offered_qps"], "stage": report["burst"]["name"],
         "ttft_p99_s": report["burst"]["ttft_p99_s"],
         "rate_429": report["burst"]["rate_429"]}
        if (report["burst"]["rate_429"] > 0
            or report["burst"]["ttft_p99_s"] > SLO_TTFT_S)
        else None
    )

    out = Path(args.out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"\nwrote {out}")
    print(f"peak concurrent streams: {gauge.peak}  "
          f"retraces_steady: {report['retraces_steady']}  "
          f"total 429s: {report['rejected_429_total']}")
    if report["retraces_steady"] > 0:
        print("!! steady-state retraces under HTTP load (bucketing leak)")
        sys.exit(1)
    if gauge.peak < clients:
        print(f"!! burst stage never reached {clients} live streams")
        sys.exit(1)
    return report


if __name__ == "__main__":
    main()
