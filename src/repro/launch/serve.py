"""Serving driver: the full StreamServe stack on the REAL JAX engine.

Everything is constructed through the public API — ``ServeConfig`` composes
the stack (arch, pairs, router, draft, speculation) and ``StreamServe``
drives it online: requests arrive over logical time, stream tokens, and one
can be cancelled or a worker killed mid-run.

  python -m repro.launch.serve --arch qwen3-1.7b --requests 12 --pairs 2
  python -m repro.launch.serve --arch mamba2-2.7b --router roundrobin \
      --spec-policy fixed --fixed-depth 5    # ablation configuration
  python -m repro.launch.serve --no-reduced  # full-size model (TPU scale)
  python -m repro.launch.serve --config serve.yaml   # flags override the file
  python -m repro.launch.serve --http --port 8080    # HTTP/SSE gateway mode
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import numpy as np

# flag -> ServeConfig field; these use default=SUPPRESS so a loaded --config
# file is only overridden by flags the user actually typed
_CONFIG_FLAGS = {
    "arch": "arch",
    "reduced": "reduced",
    "pairs": "n_pairs",
    "max_batch": "max_batch",
    "max_len": "max_len",
    "max_new": "max_new_tokens",
    "router": "router",
    "draft": "draft",
    "spec_policy": "spec_policy",
    "fixed_depth": "fixed_depth",
    "seed": "seed",
    "trace": "trace",
    "trace_dir": "trace_dir",
    "host": "gateway_host",
    "port": "gateway_port",
    "max_pending": "gateway_max_pending",
}

# CLI defaults for a quick CPU run (applied only when no --config file)
_CLI_BASE = {"max_batch": 4, "max_len": 192, "max_new_tokens": 24}


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    S = argparse.SUPPRESS
    ap.add_argument("--arch", default=S, help="model architecture (default qwen3-1.7b)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--pairs", type=int, default=S, help="stream pairs (default 2)")
    ap.add_argument("--max-batch", type=int, default=S, help="decode slots/pair (default 4)")
    ap.add_argument("--max-len", type=int, default=S, help="per-slot KV tokens (default 192)")
    ap.add_argument("--max-new", type=int, default=S, help="tokens per request (default 24)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction, default=S,
                    help="reduced CPU model (--no-reduced for full size; default on)")
    ap.add_argument("--router", default=S, help="router name (default flowguard)")
    ap.add_argument("--draft", default=S, help="draft name (default ngram)")
    ap.add_argument("--spec-policy", default=S,
                    help="speculation policy name (default specustream)")
    ap.add_argument("--fixed-depth", type=int, default=S)
    ap.add_argument("--config", default=None,
                    help="load a ServeConfig YAML (typed flags override it)")
    ap.add_argument("--dump-config", default=None,
                    help="write the resolved ServeConfig YAML and exit")
    ap.add_argument("--fail-worker", type=int, default=-1,
                    help="kill this stream pair mid-run (fault-tolerance demo)")
    ap.add_argument("--cancel-one", action="store_true",
                    help="cancel the last submitted request mid-run")
    ap.add_argument("--seed", type=int, default=S, help="PRNG seed (default 0)")
    ap.add_argument("--trace", default=S, choices=("off", "on", "flight"),
                    help="StreamTrace mode (default off)")
    ap.add_argument("--trace-dir", default=S,
                    help="directory for flight-recorder dumps")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON here after the run "
                         "(implies --trace on unless set)")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP (OpenAI-compatible /v1/completions "
                         "with SSE streaming, /metrics, /healthz) instead of "
                         "the synthetic request driver")
    ap.add_argument("--host", default=S, help="gateway bind address "
                    "(default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=S,
                    help="gateway TCP port (default 8080; 0 = ephemeral)")
    ap.add_argument("--max-pending", type=int, default=S,
                    help="gateway backpressure watermark: pending requests "
                         "beyond this get HTTP 429 (default 256)")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile every shape bucket before serving "
                         "(gateway mode: no first-request compile stall)")
    args = ap.parse_args(argv)
    if args.trace_out and not hasattr(args, "trace"):
        args.trace = "on"

    # heavy imports (jax &c) only after argument parsing
    from repro.api import ServeConfig, StreamServe
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.config:
        base = ServeConfig.from_yaml(args.config)
    else:
        base = ServeConfig(**_CLI_BASE)
    overrides = {
        field: getattr(args, flag)
        for flag, field in _CONFIG_FLAGS.items()
        if hasattr(args, flag)
    }
    cfg = base.replace(**overrides) if overrides else base
    if args.dump_config:
        cfg.to_yaml(args.dump_config)
        print(f"wrote {args.dump_config}")
        return {"config": cfg}

    serve = StreamServe(cfg)
    if args.http:
        from repro.gateway import run_gateway

        if args.warmup:
            print("warming up (pre-compiling shape buckets)...")
            serve.engine.warmup()
        run_gateway(serve, host=cfg.gateway_host, port=cfg.gateway_port)
        return {"config": cfg, "serve": serve}
    rng = np.random.default_rng(cfg.seed)
    # shared prefix so the prefix cache (C_w signal) engages
    shared = rng.integers(0, serve.arch.vocab_size, 8).tolist()
    t0 = time.perf_counter()
    handles = []
    for _ in range(args.requests):
        body = rng.integers(0, serve.arch.vocab_size, args.prompt_len - 8).tolist()
        handles.append(serve.submit(shared + body))

    # drive the engine; optionally kill a worker / cancel a request partway
    steps = 0
    killed = cancelled = False
    while serve.pending > 0:
        serve.step()
        steps += 1
        if args.fail_worker >= 0 and not killed and steps == 5:
            n = serve.fail_worker(args.fail_worker)
            killed = True
            print(f"!! killed stream pair {args.fail_worker}; re-routed {n} queued requests")
        if args.cancel_one and not cancelled and steps == 3:
            handles[-1].cancel()
            cancelled = True
            print(f"!! cancelled {handles[-1].request_id} mid-run")
        if steps > 5000:
            raise RuntimeError("engine did not drain")
    wall = time.perf_counter() - t0

    s = serve.summary()
    done = [h for h in handles if h.state.value == "finished"]
    print(f"\ncompleted {len(done)}/{args.requests} requests in {wall:.1f}s wall "
          f"({steps} engine steps)")
    print(f"logical latency mean={s['latency_mean']:.1f} p99={s['latency_p99']:.1f} "
          f"(engine ticks)")
    for w in serve.worker_stats():
        served = sum(1 for r in serve.monitor.completed if r.worker_id == w["worker_id"])
        print(f"  pair {w['worker_id']}: healthy={w['healthy']} "
              f"acceptance={w['acceptance']:.2f} cache_hit={w['cache_hit_rate']:.2f} "
              f"served={served}")
    if cfg.spec_policy == "specustream":
        depths = [w["spec_depth"] for w in serve.worker_stats() if w["spec_depth"]]
        if depths:
            print(f"speculation: adaptive, last depths {depths}")
    else:
        print(f"speculation: policy={cfg.spec_policy} depth={cfg.fixed_depth}")
    if done:
        slo = done[0].slo()

        def fmt(v, spec):
            return format(v, spec) if v is not None else "-"

        print(f"sample SLO ({slo['request_id']}): ttft={fmt(slo['ttft'], '.0f')} "
              f"tpot={fmt(slo['tpot'], '.2f')} latency={fmt(slo['latency'], '.0f')} ticks")
    if args.trace_out:
        serve.export_chrome_trace(args.trace_out)
        print(f"wrote Chrome trace to {args.trace_out} "
              f"(open in chrome://tracing or ui.perfetto.dev)")
    return {"summary": s, "serve": serve, "config": cfg}


if __name__ == "__main__":
    main()
