"""Operations and bytes the served work needs, from shapes alone.

Only live work counts: rows that hold a request, each row's own cache length,
and the tokens each row actually feeds.  Padded rows, padded verify depth and
padded prompt positions are left out, and so is the allocated cache length.
A kernel that skips work it does not need therefore reads better, and a
share of the roofline computed from these counts cannot pass 100% unless the
kernel time leaves out part of the work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from sbench.weights import Dims

BF16 = 2  # bytes per element served


def attn_pairs(cached: int, fed: int) -> int:
    """(query, key) pairs when ``fed`` tokens attend causally after ``cached``."""
    return fed * cached + fed * (fed + 1) // 2


def decode_attention_cost(m: Dims, rows: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """FLOPs and bytes of one decode-attention call (one layer) over live
    ``rows`` of ``(cached_len, fed)``: QK^T and PV, reading each row's live
    keys and values once, reading q and writing o."""
    flops = nbytes = 0.0
    for cached, fed in rows:
        flops += 4.0 * m.H * m.D * attn_pairs(cached, fed)
        nbytes += BF16 * m.D * (2 * m.K * (cached + fed) + 2 * m.H * fed)
    return flops, nbytes


def flash_attention_cost(m: Dims, prompt_lens: Iterable[int]) -> Tuple[float, float]:
    """FLOPs and bytes of one causal prefill-attention call (one layer) over
    the live prompt lengths of its rows."""
    flops = nbytes = 0.0
    for n in prompt_lens:
        flops += 4.0 * m.H * m.D * attn_pairs(0, n)
        nbytes += BF16 * m.D * n * (2 * m.H + 2 * m.K)
    return flops, nbytes


def linear_params(m: Dims) -> int:
    """Matrix parameters of one layer (q, k, v, o and the SwiGLU MLP)."""
    return m.d * m.D * (2 * m.H + 2 * m.K) + 3 * m.d * m.f


def decode_step_flops(m: Dims, rows: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs one decode/verify call needs: every fed token goes through
    every layer and the output head; attention over each row's live cache."""
    rows = list(rows)
    fed = sum(f for _, f in rows)
    attn, _ = decode_attention_cost(m, rows)
    return m.n_layers * (2.0 * linear_params(m) * fed + attn) + 2.0 * m.d * m.V * fed


def prefill_flops(m: Dims, prompt_lens: Iterable[int]) -> float:
    """Model FLOPs one prefill call needs: every prompt token through every
    layer, causal attention, and the output head at each row's last token."""
    lens = list(prompt_lens)
    attn, _ = flash_attention_cost(m, lens)
    return (m.n_layers * (2.0 * linear_params(m) * sum(lens) + attn)
            + 2.0 * m.d * m.V * len(lens))


def least_time(flops: float, nbytes: float, peak_flops: float, peak_bw: float) -> float:
    """Seconds the chip needs at the least: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)
