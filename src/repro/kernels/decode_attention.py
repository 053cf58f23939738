"""Decode attention (flash-decode style) as a Pallas TPU kernel.

Serves the speculative-verify decode step: ``T`` new tokens (1 for plain
decode, depth+1 for verification) attend to a KV cache of capacity ``S``.

Tiling
------
Grid ``(B, K, ns)`` — batch × KV head × KV blocks, the KV-block axis
sequential so the online-softmax state persists in VMEM scratch.  The
query block packs ALL ``T × G`` query rows of one KV head (GQA group size
G) into a single ``(TG, D)`` tile: decode's tiny T would otherwise leave
the MXU idle, and packing the group turns T·G vector-matrix products into
one matrix-matrix product against the shared KV block — the standard
flash-decode trick adapted to GQA.

With ``block_k = 512``, ``D = 128``, ``T·G ≤ 32``: KV tile 2×256 KiB,
scores 32×512×4B = 64 KiB — VMEM-trivial; the kernel is HBM-bandwidth
bound (it must stream the whole cache), which is exactly what the roofline
analysis predicts for decode.

Masking
-------
``kv_pos`` carries the absolute position written into every cache slot
(ring-buffer aware; -1 = empty).  Query row ``r`` (token ``t = r // G``)
sits at absolute position ``cache_len - T + t``; a slot is visible iff
``0 <= kv_pos <= q_pos`` (+ sliding-window lower bound).  Stale slots left
behind by rejected speculative tokens carry positions above the rewound
``cache_len`` and are therefore masked out — rollback needs no cache
rewrite.

Validated in ``interpret=True`` against ``ref.decode_attention`` in
tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,      # (B,) cache_len (already includes the T new tokens), SMEM
    q_ref,        # (1, 1, TGp, D)
    k_ref,        # (1, 1, bk, D)
    v_ref,        # (1, 1, bk, D)
    pos_ref,      # (1, 1, bk) absolute slot positions
    o_ref,        # (1, 1, TGp, D)
    m_ref, l_ref, acc_ref,
    *,
    T: int,
    G: int,
    scale: float,
    window: Optional[int],
    block_k: int,
):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    TGp = q_ref.shape[2]
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (TGp, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TGp, bk)

    cache_len = len_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (TGp, block_k), 0)
    t = row // G                                        # token index (pad rows -> t >= T)
    q_pos = cache_len - T + t
    kv_pos = pos_ref[0]                                 # (1, bk)
    mask = (kv_pos >= 0) & (kv_pos <= q_pos) & (row < T * G)
    if window is not None:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == ns - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "block_k", "interpret"),
)
def decode_attention_pallas(
    q: jax.Array,        # (B, T, H, D)
    k_cache: jax.Array,  # (B, S, K, D)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) valid length INCLUDING the T new tokens
    *,
    kv_positions: Optional[jax.Array] = None,  # (B, S) absolute slot positions
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, T, H, D = q.shape
    _, S, K, _ = k_cache.shape
    assert H % K == 0
    G = H // K
    scale = scale if scale is not None else D ** -0.5

    if kv_positions is None:
        # dense cache: slot i holds position i, valid iff i < cache_len
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        kv_positions = jnp.where(pos < cache_len[:, None], pos, -1)
    kv_positions = kv_positions.astype(jnp.int32)

    block_k = min(block_k, max(S, 8))
    pk = (-S) % block_k
    kh = jnp.moveaxis(k_cache, 2, 1)  # (B, K, S, D)
    vh = jnp.moveaxis(v_cache, 2, 1)
    if pk:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pk), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)), constant_values=-1)
    ns = (S + pk) // block_k

    TG = T * G
    TGp = max(8, -(-TG // 8) * 8)  # pad query rows to a multiple of 8 lanes
    # (B, T, K, G, D) -> (B, K, T*G, D): rows ordered t-major then group
    qh = q.reshape(B, T, K, G, D).transpose(0, 2, 1, 3, 4).reshape(B, K, TG, D)
    if TGp != TG:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, TGp - TG), (0, 0)))

    # Mosaic blocks must tile the last two dims by (8, 128) or span them:
    # the lengths ride in SMEM (scalar prefetch), the positions as (B, 1, S)
    # so a (1, 1, block_k) block spans its second-minor dim
    pos3 = kv_positions.reshape(B, 1, S + pk)

    kernel = functools.partial(
        _decode_kernel, T=T, G=G, scale=scale, window=window, block_k=block_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, ns),
        in_specs=[
            pl.BlockSpec((1, 1, TGp, D), lambda b, kh_, ik, ln: (b, kh_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, kh_, ik, ln: (b, kh_, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, kh_, ik, ln: (b, kh_, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, kh_, ik, ln: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, TGp, D), lambda b, kh_, ik, ln: (b, kh_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((TGp, 1), jnp.float32),
            pltpu.VMEM((TGp, 1), jnp.float32),
            pltpu.VMEM((TGp, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, TGp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(cache_len.astype(jnp.int32), qh, kh, vh, pos3)

    out = out[:, :, :TG].reshape(B, K, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, D)


def _paged_decode_kernel(
    bt_ref,       # (B, P) block table, scalar-prefetched (drives the DMA plan)
    len_ref,      # (B,) cache_len (already includes the T new tokens), SMEM
    q_ref,        # (1, 1, TGp, D)
    k_ref,        # (1, 1, ps, D) one page of one KV head
    v_ref,        # (1, 1, ps, D)
    o_ref,        # (1, 1, TGp, D)
    m_ref, l_ref, acc_ref,
    *,
    T: int,
    G: int,
    scale: float,
    window: Optional[int],
    page_size: int,
):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    TGp = q_ref.shape[2]
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (TGp, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (ps, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TGp, ps)

    cache_len = len_ref[b]
    page = bt_ref[b, ip]
    row = jax.lax.broadcasted_iota(jnp.int32, (TGp, page_size), 0)
    t = row // G                                        # token index (pad rows -> t >= T)
    q_pos = cache_len - T + t
    # page slot s of row-page-index ip holds absolute position ip*ps + s by
    # construction (positions are written exactly once, no ring wrap), so no
    # kv_pos pool is needed; page < 0 means the table entry is unallocated
    kv_pos = ip * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (TGp, page_size), 1
    )
    mask = (page >= 0) & (kv_pos <= q_pos) & (row < T * G)
    if window is not None:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ip == n_p - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "interpret"),
)
def decode_attention_paged_pallas(
    q: jax.Array,          # (B, T, H, D)
    k_pages: jax.Array,    # (n_pages, K, ps, D) global head-major page pool
    v_pages: jax.Array,
    cache_len: jax.Array,  # (B,) valid length INCLUDING the T new tokens
    block_tables: jax.Array,  # (B, P) page indices, -1 = unallocated
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-indexed flash decode over a global page pool.

    Same tiling as :func:`decode_attention_pallas` except the sequential
    axis walks the per-row block table: grid step ``(b, h, ip)`` streams
    head ``h`` of page ``block_tables[b, ip]`` of the pool — one contiguous
    ``(ps, D)`` tile, since the pool is head-major.  The table and the
    lengths are scalar-prefetched (``PrefetchScalarGridSpec``) so the page
    index is known before the DMA issues — the standard PagedAttention TPU
    pattern.  Unallocated entries
    (-1) clamp to page 0 and mask to -inf, costing one redundant page fetch
    per hole rather than a branch.
    """
    B, T, H, D = q.shape
    n_pages, K, ps, _ = k_pages.shape
    P = block_tables.shape[1]
    assert H % K == 0
    G = H // K
    scale = scale if scale is not None else D ** -0.5

    TG = T * G
    TGp = max(8, -(-TG // 8) * 8)  # pad query rows to a multiple of 8 lanes
    qh = q.reshape(B, T, K, G, D).transpose(0, 2, 1, 3, 4).reshape(B, K, TG, D)
    if TGp != TG:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, TGp - TG), (0, 0)))

    kernel = functools.partial(
        _paged_decode_kernel, T=T, G=G, scale=scale, window=window, page_size=ps
    )
    page_spec = pl.BlockSpec(
        (1, 1, ps, D),
        lambda b, h, ip, bt, ln: (jnp.maximum(bt[b, ip], 0), h, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, P),
        in_specs=[
            pl.BlockSpec((1, 1, TGp, D), lambda b, h, ip, bt, ln: (b, h, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, TGp, D), lambda b, h, ip, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((TGp, 1), jnp.float32),
            pltpu.VMEM((TGp, 1), jnp.float32),
            pltpu.VMEM((TGp, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, TGp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention_paged",
    )(block_tables.astype(jnp.int32), cache_len.astype(jnp.int32), qh,
      k_pages, v_pages)

    out = out[:, :, :TG].reshape(B, K, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, D)
