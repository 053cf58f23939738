"""The per-layer readers of the program's own spans, counters and request
stamps, against hand counts, and silent (None) where the program records
nothing for them."""
from types import SimpleNamespace

import pytest

from streambench_testlib import spec
from sbench import trace as tr


def _reduced():
    """Window [0, 1000] ns; the device is busy over [100, 300] and [600, 900]."""
    host = [["sb.window", 0.0, 1000.0, {}]]
    program = [["ss.step", 50.0, 400.0, {}], ["ss.draft", 60.0, 30.0, {}],
               ["ss.step", 500.0, 450.0, {}], ["ss.draft", 510.0, 50.0, {}],
               ["ss.step", 980.0, 100.0, {}]]
    ops = [["fusion.1 bf16[1]", 100.0, 200.0, {}], ["fusion.2 bf16[1]", 600.0, 300.0, {}]]
    return tr.Reduced({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]}],
        "program": program})


def _req(submit, start):
    return SimpleNamespace(w_submit=submit, w_prefill_start=start)


def _ctx(**kw):
    base = dict(trace=_reduced(), counters=None, requests=[], close=10.0)
    return SimpleNamespace(**{**base, **kw})


def read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_host_stall_ms():
    # idle inside each step: [50, 450] -> 400 - 200; [500, 950] -> 450 - 300; [980, 1000] -> 20
    want = 1e3 * ((200 + 150 + 20) / 1e9) / 3
    assert read("host_stall_ms.chat", _ctx()) == pytest.approx(want)


def test_draft_ms():
    assert read("draft_ms.chat", _ctx()) == pytest.approx((30 + 50) / 2 / 1e6)


def test_draft_accept_share():
    c = {"spec_proposed": 40, "spec_accepted": 6, "steps": 3}
    assert read("draft_accept_share.chat", _ctx(counters=c)) == 6 / 40
    assert read("draft_accept_share.chat", _ctx(counters=dict(c, spec_proposed=0))) is None
    assert read("draft_accept_share.chat", _ctx()) is None


def test_prefill_pad_share():
    c = {"prefill_live_tokens": 300, "prefill_slot_tokens": 512}
    assert read("prefill_pad_share.chat", _ctx(counters=c)) == 1 - 300 / 512
    assert read("prefill_pad_share.chat", _ctx(counters=dict(c, prefill_slot_tokens=0))) is None
    assert read("prefill_pad_share.chat", _ctx()) is None


def test_admit_wait_p95_ms():
    # 20 requests: waits 0.01 .. 0.19 s, and one not started by the close at 10.0
    reqs = [_req(1.0, 1.0 + 0.01 * i) for i in range(19)] + [_req(9.5, None)]
    # nearest rank p95 of 20 values is the 19th: 0.18 s; the unstarted one (0.5 s) is the 20th
    assert read("admit_wait_p95_ms.chat", _ctx(requests=reqs)) == pytest.approx(180.0)
    late = reqs[:19] + [_req(9.0, 10.5)]   # started after the close: counted to the close
    assert read("admit_wait_p95_ms.chat", _ctx(requests=late)) == pytest.approx(180.0)
    assert read("admit_wait_p95_ms.chat", _ctx(requests=[SimpleNamespace()])) is None
    assert read("admit_wait_p95_ms.chat", _ctx()) is None


def test_span_readers_silent_without_spans():
    empty = tr.Reduced({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [["sb.window", 0.0, 9.0, {}]]}]},
        {"name": "/device:TPU:0", "lines": []}]})
    assert read("host_stall_ms.chat", _ctx(trace=empty)) is None
    assert read("draft_ms.chat", _ctx(trace=empty)) is None
