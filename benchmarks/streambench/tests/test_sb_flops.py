"""FLOP and byte counts against hand counts, live-length counting, and the
roofline share of a kernel that skips unused blocks staying under 100%."""
import math

import pytest

from streambench_testlib import BENCH, FIX, spec
from sbench import flops

QWEN3_CFG = spec.load_json(BENCH / "configs" / "qwen3-1.7b.json")
QW = spec.family(QWEN3_CFG)
QWEN3 = QW.Dims.of(QWEN3_CFG)
QWEN2 = QW.Dims.of(spec.load_json(FIX / "qwen2-tiny.json"))   # 20/4 heads, padded to 32
PEAK_F, PEAK_B = 197e12, 819e9


def test_attention_pairs_hand_count():
    assert flops.attn_pairs(0, 1) == 1
    assert flops.attn_pairs(0, 4) == 1 + 2 + 3 + 4
    assert flops.attn_pairs(10, 3) == 11 + 12 + 13


def test_decode_attention_hand_count():
    # one row, 100 cached, 1 fed: 101 keys, qk^T and pv are 2*D each per (q, k)
    f, b = QW.decode_attention_cost(QWEN3, [(100, 1)])
    assert f == 4 * 16 * 128 * 101
    assert b == 2 * 128 * (2 * 8 * 101 + 2 * 16 * 1)


def test_live_rows_only():
    one = QW.decode_attention_cost(QWEN3, [(300, 5)])
    two = QW.decode_attention_cost(QWEN3, [(300, 5), (300, 5)])
    assert two == (2 * one[0], 2 * one[1])
    assert QW.decode_attention_cost(QWEN3, []) == (0.0, 0.0)
    # the allocated 2048 never enters: a short row costs less than a long one
    assert QW.decode_attention_cost(QWEN3, [(50, 1)])[1] < \
        QW.decode_attention_cost(QWEN3, [(2000, 1)])[1]


def test_flash_attention_hand_count():
    f, b = QW.flash_attention_cost(QWEN2, [4])
    assert f == 4 * 20 * 8 * 10            # 20 real heads, not the 32 padded
    assert b == 2 * 8 * 4 * (2 * 20 + 2 * 4)


def test_linear_params_match_published_sizes():
    # qwen3-1.7b: 28 layers of q/k/v/o + MLP, plus a tied 151936 x 2048 table
    total = 28 * QW.linear_params(QWEN3) + 151936 * 2048
    assert 1.70e9 < total + 28 * (2 * 2048 + 2 * 128) < 1.73e9
    per_layer = QW.linear_params(QWEN2)
    assert per_layer == 64 * 8 * (40 + 8) + 3 * 64 * 128


def test_step_flops_hand_count():
    rows = [(10, 2)]
    attn, _ = QW.decode_attention_cost(QWEN3, rows)
    want = 28 * (2 * QW.linear_params(QWEN3) * 2 + attn) + 2 * 2048 * 151936 * 2
    assert QW.decode_step_flops(QWEN3_CFG, rows) == want
    lens = [3, 5]
    attn, _ = QW.flash_attention_cost(QWEN3, lens)
    want = 28 * (2 * QW.linear_params(QWEN3) * 8 + attn) + 2 * 2048 * 151936 * 2
    assert QW.prefill_flops(QWEN3_CFG, lens) == want


@pytest.mark.parametrize("block", [16, 128, 512])
def test_roofline_of_block_skipping_kernel_stays_under_100(block):
    """A kernel that reads whole blocks of keys up to each row's live length
    (skipping the rest), running at the chip's peaks, takes at least the
    least time of the live work: its share reads <= 100%, and reaches 100%
    only when every row ends on a block edge."""
    rows = [(37, 1), (500, 9), (1023, 4), (1, 1)]
    need = flops.least_time(*QW.decode_attention_cost(QWEN3, rows), PEAK_F, PEAK_B)
    touched = [(math.ceil((c + f) / block) * block - f, f) for c, f in rows]
    kernel_time = flops.least_time(*QW.decode_attention_cost(QWEN3, touched), PEAK_F, PEAK_B)
    share = 100 * need / kernel_time
    assert 0 < share <= 100
    aligned = [(block - 1, 1), (2 * block - 1, 1)]
    t = flops.least_time(*QW.decode_attention_cost(QWEN3, aligned), PEAK_F, PEAK_B)
    assert 100 * t / t == 100
    # a kernel that walks the whole allocated cache reads lower still
    full = [(2048 - f, f) for _, f in rows]
    t_full = flops.least_time(*QW.decode_attention_cost(QWEN3, full), PEAK_F, PEAK_B)
    assert 100 * need / t_full < share
