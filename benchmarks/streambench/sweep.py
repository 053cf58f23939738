"""Find an open-loop cell's knee on the chip: the highest offered rate whose
backlog does not grow across the window.

    python benchmarks/streambench/sweep.py --workload qwen3-1.7b.chat \
        --rates 2,4,6,8 --seconds 20 --seed 1

One process, one engine: for each rate, in the order given, the cell's
traffic mix is offered at that rate for its warm-in and ``--seconds``, the
number of requests waiting for admission is read after every step, and the
engine is drained before the next rate.  The backlog grew where the
least-squares slope of that count over the window, times the window, is
more than ``max(2, 5% of the requests due in the window)``.  Prints one
table row per rate and, last, a JSON object with the rows and the knee.
The cell's ``rate_per_s`` is then set by hand to 0.6 of the knee.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def backlog_growth(samples, lo: float, hi: float) -> float:
    pts = [(t, n) for t, n in samples if lo <= t <= hi]
    if len(pts) < 2:
        return 0.0
    mt = sum(t for t, _ in pts) / len(pts)
    mn = sum(n for _, n in pts) / len(pts)
    var = sum((t - mt) ** 2 for t, _ in pts)
    slope = sum((t - mt) * (n - mn) for t, n in pts) / var if var else 0.0
    return slope * (hi - lo)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from sbench import harness, program, spec
    from sbench.traffic import make_plan

    cell = spec.load_cell(args.workload)
    try:
        dev = harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    cfg, mix = cell.config, cell.traffic
    serve = program.build(cell.family, cfg, cell.family.make_weights(cfg, args.seed))
    serve.engine.warmup(max_prompt_len=int(mix["prompt"]["max"]))
    print(f"sweep {cell.name} on {dev}: set-up {time.perf_counter() - T_START:.1f}s",
          file=sys.stderr, flush=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        plan = make_plan(mix, args.seed, args.seconds, int(cfg["vocab_size"]), rate)
        origin = harness.clock()
        drv = harness.Driver(serve, plan, origin, lambda name: harness.NULL)
        samples = []
        step = serve.step

        def counted_step():
            n = step()
            samples.append((harness.clock(), serve.engine.scheduler.pending_total()))
            return n

        serve.step = counted_step
        w0 = origin + float(mix["warm_in_s"])
        t_close = drv.drive(w0 + args.seconds)
        serve.step = step
        st = harness.window_stats(drv, w0, t_close)
        growth = backlog_growth(samples, w0, t_close)
        row = {"rate_per_s": rate, "due_in_window": st["attempted"],
               "backlog_growth": growth,
               "grew": growth > max(2.0, 0.05 * st["attempted"]),
               "tokens_per_s": st["tokens"] / st["seconds"],
               "ttft_p95_s": harness.percentile(st["ttft"], 95),
               "tpot_p95_s": harness.percentile(st["tpot"], 95),
               "gen_lag_p95_s": harness.percentile(st["lag"], 95)}
        rows.append(row)
        print(f"sweep rate={rate} " + " ".join(f"{k}={v}" for k, v in row.items()),
              flush=True)
        t_drain = harness.clock()
        while not serve.engine.drained() and harness.clock() - t_drain < 120:
            for q in drv.live:   # cancel what is left: the next rate starts empty
                serve.cancel(q.r.request_id)
            drv.live = []
        if not serve.engine.drained():
            raise SystemExit("engine did not drain between rates")
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if r["grew"]:
            break
        knee = r["rate_per_s"]
    print(json.dumps({"workload": cell.name, "rows": rows, "knee_rate_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
