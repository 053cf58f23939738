"""Readings that set a cell's correctness limit, on the chip.

    python benchmarks/streambench/control.py --workload qwen3-1.7b.chat \
        --seeds 101,102,103 --seconds 20

For each seed, in this one process: a run of the cell at its own load for
``--seconds`` (the timed path, as ``run.py`` drives it), then the reference
over the same sample of finished requests, reading both the program's widest
logit gap and that of the control, the reference computed on int8 values
(the step below the configuration's bfloat16).  The limit goes above every
program reading and below every control reading.  Prints a row per seed and,
last, a JSON object with all readings.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default="int8")
    args = ap.parse_args(argv)

    from sbench import harness, spec

    cell = spec.load_cell(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = harness.run(cell, seed, args.seconds, False, time.perf_counter(),
                            control=args.control)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "program": r["checks"]["max_logit_gap"]["value"],
               "control": r["control"]["max_logit_gap"],
               "compared": r["checks"]["served_tokens_compared"]["value"]}
        rows.append(row)
        print("control " + " ".join(f"{k}={v}" for k, v in row.items()), flush=True)
        gc.collect()
    print(json.dumps({"workload": cell.name, "control": args.control, "rows": rows,
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
