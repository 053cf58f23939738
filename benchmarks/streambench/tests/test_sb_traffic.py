"""The traffic generator: determinism by seed, bounds, the same work in every
block for every seed, and the open-loop schedule."""
import numpy as np
import pytest

from streambench_testlib import BENCH, FIX, spec
from sbench.traffic import exp_gaps, make_plan, quantile_lengths

CHAT = spec.load_json(BENCH / "traffic" / "chat.json")
TINY = spec.load_json(FIX / "tiny-chat.json")
BIG = 2**31 + 12345


@pytest.mark.parametrize("mix", [CHAT, TINY], ids=["chat", "tiny"])
def test_same_seed_same_plan(mix):
    a = make_plan(mix, BIG, 40, 151936, rate=5.0)
    b = make_plan(mix, BIG, 40, 151936, rate=5.0)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.answers, b.answers)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts, strict=True))


@pytest.mark.parametrize("mix", [CHAT, TINY], ids=["chat", "tiny"])
def test_seeds_share_the_work_in_another_order(mix):
    a = make_plan(mix, 1, 40, 151936, rate=5.0)
    b = make_plan(mix, 2, 40, 151936, rate=5.0)
    la, lb = [len(p) for p in a.prompts], [len(p) for p in b.prompts]
    assert sorted(la) == sorted(lb) and la != lb
    assert sorted(a.answers) == sorted(b.answers)
    assert np.allclose(np.sort(np.diff(a.due, prepend=0)), np.sort(np.diff(b.due, prepend=0)))
    assert not np.array_equal(a.prompts[0][:8], b.prompts[0][:8])


@pytest.mark.parametrize("seed", [3, BIG])
def test_every_block_holds_the_same_work(seed):
    k = CHAT["block"]
    plan = make_plan(CHAT, seed, 51, 151936, rate=1.5)
    lens = np.array([len(p) for p in plan.prompts]).reshape(-1, k)
    answers = plan.answers.reshape(-1, k)
    gaps = np.diff(plan.due, prepend=0).reshape(-1, k)
    assert len(lens) >= 2
    for row in range(1, len(lens)):
        assert sorted(lens[row]) == sorted(lens[0])
        assert sorted(answers[row]) == sorted(answers[0])
        assert np.allclose(np.sort(gaps[row]), np.sort(gaps[0]))
        assert not np.array_equal(lens[row], lens[0])   # each block in its own order
    # a block's gaps span exactly block / rate seconds
    assert np.allclose(gaps.sum(1), k / 1.5)


@pytest.mark.parametrize("mix", [CHAT, TINY], ids=["chat", "tiny"])
def test_lengths_and_ids_in_bounds(mix):
    vocab = 151936
    plan = make_plan(mix, 7, 40, vocab, rate=5.0)
    lens = np.array([len(p) for p in plan.prompts])
    assert lens.min() >= mix["prompt"]["min"] and lens.max() <= mix["prompt"]["max"]
    assert plan.answers.min() >= mix["answer"]["min"]
    assert plan.answers.max() <= mix["answer"]["max"]
    ids = np.concatenate(plan.prompts)
    assert ids.min() >= 0 and ids.max() < vocab


def test_chat_fits_the_decode_slot():
    """Prompt, answer and the deepest verify step stay inside a slot."""
    cfg = spec.load_json(BENCH / "configs" / "qwen3-1.7b.json")
    assert CHAT["prompt"]["max"] + CHAT["answer"]["max"] + 8 + 1 <= cfg["serve"]["max_len"]


def test_open_loop_schedule():
    plan = make_plan(CHAT, 3, 40, 1000, rate=5.0)
    assert np.all(np.diff(plan.due) > 0)
    # enough requests for the warm-in and the whole window at this rate
    assert plan.due[-1] > CHAT["warm_in_s"] + 40
    assert len(plan) % CHAT["block"] == 0
    assert abs(len(plan) / plan.due[-1] - 5.0) < 1e-9
    with pytest.raises(ValueError):
        make_plan(CHAT, 3, 40, 1000, rate=0.0)  # an open loop needs the cell's rate
    with pytest.raises(ValueError):
        make_plan(dict(CHAT, arrival="closed"), 3, 40, 1000, rate=5.0)


def test_quantile_multisets():
    lens = quantile_lengths({"dist": "lognormal", "median": 100, "sigma": 0.5,
                             "min": 10, "max": 1000}, 1001)
    assert lens[500] == 100 and np.all(np.diff(lens) >= 0)
    uni = quantile_lengths({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert uni.min() == 16 and uni.max() == 64
    for n in (16, 10000):
        assert exp_gaps(4.0, n).mean() == pytest.approx(0.25)
        assert np.all(np.diff(exp_gaps(4.0, n)) > 0)
