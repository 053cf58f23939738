"""Public kernel API with backend dispatch.

On TPU the Pallas kernels are used; on every other backend the chunked
pure-jnp references run (``interpret=True`` runs the Pallas kernels in the
interpreter instead, which is how the CPU tests cover them).  On a TPU only
an explicit ``force_ref=True`` argument picks the reference.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    force_ref: bool = False,
    interpret: bool = False,
):
    """Prefill / training attention.  See ref.flash_attention for shapes."""
    if not force_ref and (interpret or _use_pallas()):
        from repro.kernels import flash_attention as fa

        return fa.flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale,
            interpret=interpret,
        )
    return ref.flash_attention(q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale)


def decode_attention(
    q,
    k_cache,
    v_cache,
    cache_len,
    *,
    kv_positions=None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    causal: bool = True,
    force_ref: bool = False,
    interpret: bool = False,
):
    """Decode-step attention of T new tokens against a KV cache."""
    if causal and not force_ref and (interpret or _use_pallas()):
        from repro.kernels import decode_attention as da

        return da.decode_attention_pallas(
            q, k_cache, v_cache, cache_len, kv_positions=kv_positions,
            window=window, scale=scale, interpret=interpret,
        )
    return ref.decode_attention(
        q, k_cache, v_cache, cache_len, kv_positions=kv_positions, window=window,
        scale=scale, causal=causal,
    )


def decode_attention_paged(
    q,
    k_pages,
    v_pages,
    cache_len,
    block_tables,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    force_ref: bool = False,
    interpret: bool = False,
):
    """Decode-step attention over a paged KV pool via per-row block tables."""
    if not force_ref and (interpret or _use_pallas()):
        from repro.kernels import decode_attention as da

        return da.decode_attention_paged_pallas(
            q, k_pages, v_pages, cache_len, block_tables, window=window,
            scale=scale, interpret=interpret,
        )
    return ref.decode_attention_paged(
        q, k_pages, v_pages, cache_len, block_tables, window=window, scale=scale,
    )


def ssd_scan(
    x,
    dt,
    A,
    Bm,
    C,
    *,
    chunk: int = 256,
    initial_state=None,
    return_state: bool = False,
    force_ref: bool = False,
    interpret: bool = False,
):
    """Chunked Mamba2 SSD scan."""
    if not force_ref and (interpret or _use_pallas()):
        from repro.kernels import ssd_scan as sk

        return sk.ssd_scan_pallas(
            x, dt, A, Bm, C, chunk=chunk, initial_state=initial_state,
            return_state=return_state, interpret=interpret,
        )
    return ref.ssd_scan(
        x, dt, A, Bm, C, chunk=chunk, initial_state=initial_state, return_state=return_state
    )


ssd_decode_step = ref.ssd_decode_step  # single-token recurrence is trivially small
