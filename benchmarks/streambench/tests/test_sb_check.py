"""The correctness check at a CPU size: a sound run passes it, and the
control (the reference on int8 values in the program's place) fails it,
on the same served tokens, for both block types."""
import time

import pytest

from streambench_testlib import quiet, tiny_cell
from sbench import harness


@pytest.mark.parametrize("config,mix", [("qwen3-tiny", "tiny-chat"), ("qwen2-tiny", "tiny-chat")])
def test_sound_run_passes_and_control_fails(config, mix):
    cell = tiny_cell(config, mix)
    r = harness.run(cell, 2**31 + 101, 2.0, False, time.perf_counter(),
                    require_chip=False, cache=False, control="int8", log=quiet)
    limit = cell.data["max_logit_gap"]
    checks = r["checks"]
    assert r["correct"] is True, checks
    assert checks["max_logit_gap"]["value"] <= limit
    assert checks["answer_length_mismatches"]["value"] == 0
    assert checks["served_tokens_compared"]["value"] >= cell.traffic["ref_min_tokens"]
    assert r["control"]["max_logit_gap"] > limit
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
