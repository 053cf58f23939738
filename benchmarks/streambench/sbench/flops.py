"""What every family's counts of operations and bytes share.

A family module (``families/<family>.py``) counts the FLOPs and bytes of its
own calls from shapes alone, and only live work: rows that hold a request,
each row's own cache length, and the tokens each row actually feeds.  Padded
rows, padded verify depth, padded heads and padded prompt positions are left
out, and so is the allocated cache length.  A kernel that skips work it does
not need therefore reads better, and a share of the roofline computed from
these counts cannot pass 100% unless the kernel time leaves out part of the
work.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Tuple

BF16 = 2  # bytes per element served


def attn_pairs(cached: int, fed: int) -> int:
    """(query, key) pairs when ``fed`` tokens attend causally after ``cached``."""
    return fed * cached + fed * (fed + 1) // 2


def least_time(flops: float, nbytes: float, peak_flops: float, peak_bw: float) -> float:
    """Seconds the chip needs at the least: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)


def kernel_needs(cost: Callable[[Dict[str, Any], Any], Tuple[int, float, float]],
                 cfg: Dict[str, Any], works: Iterable[Any],
                 peaks: Dict[str, float]) -> List[Tuple[int, float]]:
    """Least device seconds of one kernel's calls over the program executions
    whose work (a decode call's live rows, a prefill call's prompt lengths) is
    in ``works``.  ``cost(cfg, work)`` gives one execution's ``(calls, FLOPs
    of one call, bytes of one call)``.  Returns ``(calls per execution, summed
    least time of one call)`` for each number of calls met; the kernel's least
    time is the sum of ``calls * seconds``."""
    pf, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    sums: Dict[int, float] = {}
    for work in works:
        calls, flops, nbytes = cost(cfg, work)
        sums[calls] = sums.get(calls, 0.0) + least_time(flops, nbytes, pf, bw)
    return list(sums.items())
