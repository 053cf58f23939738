"""Percentiles and window accounting on the host clock: who is in the TTFT
tail, which tokens count, and a request still waiting at the close."""
from types import SimpleNamespace

import pytest

from streambench_testlib import ROOT  # noqa: F401  (sets the import paths)
from sbench.harness import end_to_end, percentile, window_stats


def test_nearest_rank_percentile():
    assert percentile([], 95) is None
    assert percentile([1, 2, 3, 4], 50) == 2
    vals = list(range(1, 201))
    assert percentile(vals, 95) == 190
    assert percentile(vals, 100) == 200
    assert percentile([5.0], 95) == 5.0


def _req(i, due, submit, prompt_tick, first_tick, token_ticks, state="finished"):
    r = SimpleNamespace(t_prefill_start=prompt_tick, t_first_token=first_tick,
                        token_times=token_ticks, state=SimpleNamespace(value=state))
    return SimpleNamespace(i=i, due=due, submit=submit, r=r)


def _driver(reqs, ends, starts):
    drv = SimpleNamespace(reqs=reqs, tick_end=ends, tick_start=starts)
    drv.when = lambda t, start=False: None if t is None else (starts if start else ends).get(t)
    return drv


def test_window_accounting():
    # ticks 1..4 end at 1.0, 2.0, 3.0, 4.0 s; the window is [1.5, 3.5)
    ends = {1.0: 1.0, 2.0: 2.0, 3.0: 3.0, 4.0: 4.0}
    starts = {t: v - 0.5 for t, v in ends.items()}
    reqs = [
        # due before the window: its tokens in the window count, its TTFT does not
        _req(0, 0.2, 0.2, 1.0, 1.0, [1.0, 2.0, 3.0]),
        # due in the window, first token at 3.0 s, finished at 3.0: TTFT 1.2
        _req(1, 1.8, 1.85, 3.0, 3.0, [3.0], state="finished"),
        # due in the window and still waiting at the close: enters with its wait
        _req(2, 2.5, 2.6, None, None, [], state="queued"),
        # due after the window: not attempted
        _req(3, 3.6, 3.6, None, None, [], state="queued"),
    ]
    st = window_stats(_driver(reqs, ends, starts), 1.5, 3.5)
    assert st["attempted"] == 2 and st["failed"] == 0
    assert st["ttft"] == pytest.approx([1.2, 1.0])
    assert st["lag"] == pytest.approx([0.05, 0.1])
    assert st["qwait"] == pytest.approx([0.7, 1.0])
    # tokens at 2.0 and 3.0 (request 0) and 3.0 (request 1)
    assert st["tokens"] == 3
    # request 0 finished inside the window: (3.0 - 1.0) / 2 tokens gaps
    assert st["tpot"] == pytest.approx([1.0])
    assert end_to_end("ttft_p95_s", st, 9.0) == pytest.approx(1.2)
    assert end_to_end("ttft_p50_s", st, 9.0) == pytest.approx(1.0)
    assert end_to_end("setup_s", st, 9.0) == 9.0
    for gone in ("goodput", "tokens_per_s"):
        with pytest.raises(KeyError):
            end_to_end(gone, st, 9.0)


def test_stall_at_the_close_is_not_hidden():
    ends = {1.0: 1.0}
    reqs = [_req(i, 1.0 + 0.1 * i, 1.0 + 0.1 * i, None, None, [], state="queued")
            for i in range(10)]
    st = window_stats(_driver(reqs, ends, {1.0: 0.5}), 0.5, 5.0)
    assert max(st["ttft"]) == pytest.approx(4.0)
    assert end_to_end("ttft_p95_s", st, 0.0) == pytest.approx(4.0)
