"""The trace reduction against a small recorded trace: about 50 ms of a
``qwen3-1.7b`` run on one TPU v5e (one decode program with its host spans),
kept as ``fixtures/trace_v5e.json``, and a hand-built profile with the
program's ``ss.*`` spans beside the harness's ``sb.*``.  Each number is
checked against a plain recount of the same events."""
from types import SimpleNamespace

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


# ---------------------------------------------------------------- program spans
def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _profile():
    """A ``ProfileData`` stand-in: two TPU planes, the host plane with the
    harness's and the program's spans and a host event of neither, and a
    plane the reduction does not read."""
    def plane(name, lines):
        return SimpleNamespace(name=name, lines=[SimpleNamespace(name=n, events=e)
                                                 for n, e in lines])
    ops0 = [_ev("%fusion.1 = bf16[4]{0} fusion(%a)", 1100, 100),
            _ev("%copy.2 = bf16[4]{0} copy(%b)", 1150, 150),
            _ev("%decode_attention.3 = bf16[4]{0} custom-call(%c)", 1500, 100),
            _ev("%fusion.4 = bf16[4]{0} fusion(%d)", 1950, 150)]
    return SimpleNamespace(planes=[
        plane("/device:TPU:0", [("XLA Ops", ops0),
                                ("XLA Modules", [_ev("jit__lane_decode(1)", 1100, 500)]),
                                ("Steps", [_ev("0", 1000, 1000)])]),
        plane("/device:TPU:1", [("XLA Ops", [_ev("%fusion.9 = f32[1]{0} fusion()", 1000, 1000)])]),
        plane("/host:CPU", [
            ("python3", [_ev("sb.window", 1000, 1000), _ev("ss.step", 1050, 350),
                         _ev("ss.draft", 1460, 20), _ev("ss.step", 1450, 250),
                         _ev("PjitFunction(_lane_decode)", 1060, 5), _ev("sb.step", 1040, 700)]),
            ("worker", [_ev("ss.step", 900, 120), _ev("ss.step", 1900, 150)])]),
        plane("/host:metadata", [("x", [_ev("ss.step", 0, 1)])]),
    ])


@pytest.fixture
def hand(tmp_path, monkeypatch):
    import jax.profiler

    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        SimpleNamespace(from_file=lambda path: _profile()))
    return tr.load_xspace(str(tmp_path))


def test_load_xspace_keeps_program_spans_apart(hand):
    host = [p for p in hand["planes"] if p["name"] == "/host:CPU"][0]
    harness_spans = [e[0] for ln in host["lines"] for e in ln["events"]]
    assert harness_spans == ["sb.window", "sb.step"]   # the sb.* list as before
    assert [(n, s, d) for n, s, d, _ in hand["program"]] == [
        ("ss.step", 900, 120), ("ss.step", 1050, 350), ("ss.step", 1450, 250),
        ("ss.draft", 1460, 20), ("ss.step", 1900, 150)]
    dev = [p for p in hand["planes"] if p["name"] == "/device:TPU:0"][0]
    assert [ln["name"] for ln in dev["lines"]] == ["XLA Ops", "XLA Modules"]
    assert dev["lines"][0]["events"][0][0] == "fusion.1 bf16[4]"
    assert "/host:metadata" not in [p["name"] for p in hand["planes"]]


def test_spans_named_and_idle_inside_hand_counts(hand):
    red = tr.Reduced(hand)
    # spans that start inside the window [1000, 2000), from either list
    assert red.spans_named("ss.step") == [(1050, 1400), (1450, 1700), (1900, 2050)]
    assert red.spans_named("ss.draft") == [(1460, 1480)]
    assert red.spans_named("sb.step") == [(1040, 1740)]
    assert red.spans_named("ss.nothing") == []
    # device 0 is busy over [1100, 1300], [1500, 1600], [1950, 2000]; device 1 all along
    assert red.idle_inside(1050, 1400) == pytest.approx((350 - 200) / 2 / 1e9)
    assert red.idle_inside(1450, 1700) == pytest.approx((250 - 100) / 2 / 1e9)
    assert red.idle_inside(1900, 2050) == pytest.approx((100 - 50) / 2 / 1e9)   # clipped
    assert red.idle_inside(900, 1020) == pytest.approx(20 / 2 / 1e9)
    assert red.idle_inside(1120, 1280) == 0.0
    assert red.idle_inside(2100, 2200) == 0.0
    total = red.idle_inside(red.lo, red.hi)
    assert total == pytest.approx(red.window_s - red.busy_s)
    # the harness's labels of idle gaps are as before: sb.* spans only
    assert set(dict(red.idle_gaps())) <= {"none", "sb.step"}


def test_idle_inside_agrees_with_brute_force_on_the_recorded_trace(red):
    busy = _timeline(red)
    lo = int(red.lo)
    for a, b in [(red.lo, red.hi), (red.lo + 1e6, red.lo + 7e6), (red.lo + 3e7, red.lo + 3.3e7)]:
        a_i, b_i = int(a) - lo, int(b) - lo
        want = ((b_i - a_i) - busy[a_i:b_i].sum()) / 1e9
        assert red.idle_inside(a, b) == pytest.approx(want, abs=2e-9 * len(red.ops[0]))
