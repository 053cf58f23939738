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


@pytest.mark.parametrize("trace", [False, True])
def test_program_is_freed_before_the_reference(trace, monkeypatch):
    """Nothing of the run keeps the engine alive into the check, so the
    reference's weights fit where the program's were (a traced run too)."""
    import gc
    import weakref

    from sbench import program, trace as tr

    engines = []
    build = program.build

    def recorded(*a, **kw):
        serve = build(*a, **kw)
        engines.append(weakref.ref(serve.engine))
        return serve

    alive = []
    gaps = harness.reference_gaps

    def checked(*a, **kw):
        gc.collect()
        alive.append(engines[0]() is not None)
        return gaps(*a, **kw)

    load = tr.load_xspace

    def with_device_plane(path):   # the CPU's trace has no TPU plane
        data = load(path)
        t = [e for p in data["planes"] for ln in p["lines"] for e in ln["events"]
             if e[0] == "sb.window"][0][1]
        data["planes"].append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1 f32[1]", t + 1e6, 1e6, {}]]}]})
        return data

    monkeypatch.setattr(program, "build", recorded)
    monkeypatch.setattr(harness, "reference_gaps", checked)
    monkeypatch.setattr(tr, "load_xspace", with_device_plane)
    monkeypatch.setattr(harness, "load_peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell = tiny_cell()
    r = harness.run(cell, 2**31 + 103, 1.5, trace, time.perf_counter(),
                    require_chip=False, cache=False, log=quiet)
    assert alive == [False]
    assert r["correct"] is True


def test_sample_fills_to_the_mix_s_ref_tokens(monkeypatch):
    """The check takes finished requests until the mix's ``ref_tokens`` served
    tokens are in, up to ``ref_max_requests``, and compares all of them."""
    import numpy as np

    seen = []

    def sample_only(cell, seed, sample, control=None):
        seen.append(sample)
        return [np.zeros(len(s)) for _, s in sample], None

    monkeypatch.setattr(harness, "reference_gaps", sample_only)
    cell = tiny_cell()
    cell.traffic = dict(cell.traffic, ref_tokens=10**6, ref_max_requests=5)
    r = harness.run(cell, 2**31 + 107, 1.5, False, time.perf_counter(),
                    require_chip=False, cache=False, log=quiet)
    assert len(seen[0]) == 5
    assert r["checks"]["served_tokens_compared"]["value"] == sum(len(s) for _, s in seen[0])
