"""The trace reduction against a small recorded trace: about 50 ms of a
``qwen3-1.7b`` run on one TPU v5e (one decode program with its host spans),
kept as ``fixtures/trace_v5e.json``.  Each number is checked against a plain
recount of the same events."""
import numpy as np
import pytest

from streambench_testlib import FIX, spec
from sbench import trace as tr

DATA = spec.load_json(FIX / "trace_v5e.json")


@pytest.fixture(scope="module")
def red():
    return tr.Reduced(DATA)


def _device_ops():
    dev = [p for p in DATA["planes"] if p["name"].startswith("/device:TPU:")][0]
    return [e for ln in dev["lines"] if ln["name"] == "XLA Ops" for e in ln["events"]]


def _timeline(red):
    """1 ns resolution busy mask over the window, by brute force."""
    lo, hi = int(red.lo), int(red.hi)
    busy = np.zeros(hi - lo, bool)
    for _, s, d, _ in _device_ops():
        a, b = max(int(s), lo), min(int(s + d), hi)
        if b > a:
            busy[a - lo:b - lo] = True
    return busy


def test_busy_and_idle_share(red):
    busy = _timeline(red)
    assert red.window_s == pytest.approx(len(busy) / 1e9)
    assert red.busy_s == pytest.approx(busy.sum() / 1e9, abs=2e-9 * len(red.ops[0]))
    assert 0 < red.busy_s < red.window_s


def test_kernel_time_by_name(red):
    want = sum(d for name, s, d, _ in _device_ops()
               if name.split(" ")[0].rsplit(".", 1)[0] == "decode_attention"
               and red.lo <= s and s + d <= red.hi) / 1e9
    assert want > 0
    assert red.kernel_s("decode_attention") == pytest.approx(want)
    assert red.kernel_s("decode") == 0.0           # names match whole, not by prefix
    assert red.kernel_s("flash_attention") == 0.0  # no prefill in this slice


def test_program_runs(red):
    runs = red.module_runs("_lane_decode")
    assert len(runs) >= 1 and all(r > 0 for r in runs)


def test_idle_gaps_by_host_span(red):
    gaps = dict(red.idle_gaps(n=100))
    idle = red.window_s - red.busy_s
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6, abs=1e-9)
    assert set(gaps) <= {"none"} | {n for _, _, n in red.spans}
    # recount: label each idle nanosecond's gap by the innermost span at its middle
    busy = _timeline(red)
    edges = np.flatnonzero(np.diff(np.r_[1, busy.astype(np.int8), 1]))
    spans = [(s, e, n) for s, e, n in red.spans if n != "sb.window"]
    recount = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        t = red.lo + (a + b) / 2
        inside = [(e - s, n) for s, e, n in spans if s <= t <= e]
        label = min(inside)[1] if inside else "none"
        recount[label] = recount.get(label, 0.0) + (b - a) / 1e9
    for k, v in recount.items():
        assert gaps[k] == pytest.approx(v, rel=1e-3, abs=1e-8)


def test_top_ops_leave_out_containers(red):
    top = red.top_ops(n=10)
    assert top and all(not name.split("/", 1)[1].startswith("while") for name, _ in top)
    assert sum(v for _, v in top) <= red.busy_s * 1.0001
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
