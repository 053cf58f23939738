"""Shared helpers of the benchmark's CPU tests: import paths and tiny cells."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
FIX = HERE / "fixtures"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from sbench import spec  # noqa: E402

TINY_E2E = [{"name": "ttft_p95_s", "unit": "s"}, {"name": "tpot_p95_s", "unit": "s"},
            {"name": "setup_s", "unit": "s"}]


# max_logit_gap limits at this size, between what sound runs and the int8
# control read on the CPU (widest gap over ~200 served tokens; also with
# four runs sharing the cores):
#   qwen3-tiny chat  : program 0.0010-0.0101 over seeds 20-27, 0.0019 at
#                      2**31+101; control 0.0082-0.0166 at 2**31+101
#   qwen2-tiny chat  : program 0.0010-0.0064 over seeds 20-27, 0.0040-0.0051
#                      at 2**31+101; control 0.0295 at 2**31+101
TINY_LIMIT = {("qwen3-tiny", "tiny-chat"): 0.005, ("qwen2-tiny", "tiny-chat"): 0.009}


def tiny_cell(config: str = "qwen3-tiny", mix: str = "tiny-chat", rate: float = 8.0) -> spec.Cell:
    """A cell at a size a CPU test holds (test_sb_check.py, test_sb_faults.py)."""
    return spec.Cell(f"tiny.{mix}", 1, spec.load_json(FIX / f"{config}.json"),
                     spec.load_json(FIX / f"{mix}.json"),
                     {"rate_per_s": rate, "max_logit_gap": TINY_LIMIT[(config, mix)]},
                     list(TINY_E2E), [])


def quiet(_msg: str) -> None:
    pass
