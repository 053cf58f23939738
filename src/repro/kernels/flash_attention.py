"""Flash attention (prefill / training) as a Pallas TPU kernel.

Tiling
------
Grid ``(B, H, nq, nk)``; the last axis (KV blocks) is sequential
("arbitrary" dimension semantics) so the online-softmax running state —
``m`` (row max), ``l`` (row sum), ``acc`` (output accumulator) — lives in
VMEM scratch and is carried across KV blocks of one (batch, head, q-block)
cell.  Blocks are sized for VMEM: with ``block_q = block_k = 512`` and
``D = 128`` the working set is

    q:  512*128*4B  = 256 KiB      k, v: 2 * 512*128*4B = 512 KiB
    acc: 512*128*4B = 256 KiB      scores: 512*512*4B   = 1 MiB

well under the ~16 MiB/core VMEM budget of v5e, leaving room for the
double-buffered DMA pipeline that the Pallas runtime inserts between HBM and
VMEM.  All matmul dims are multiples of the 128-lane MXU tiling.

GQA is expressed in the index maps: query head ``h`` reads KV head
``h // group_size`` — no repeated KV materialisation in HBM (the repeat
happens implicitly through block indexing).

Causal + sliding-window masking is positional (absolute positions from
``q_offset``), computed on 2D iota inside the kernel.  Fully-masked KV
blocks short-circuit through ``pl.when`` (the DMA still runs; the MXU work
is skipped).

Validated in ``interpret=True`` mode against ``ref.attention_naive`` over
shape/dtype/window sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref,            # blocks: (bq, D), (bk, D), (bk, D)
    o_ref,                          # (bq, D)
    m_ref, l_ref, acc_ref,          # scratch: (bq, 1), (bq, 1), (bq, D)
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    block_q: int,
    block_k: int,
    seq_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # Whole-block skip test (saves MXU work on fully masked blocks).
    block_needed = True
    if causal:
        # first q row of this block vs last k row of this block
        block_needed = (q_offset + iq * block_q + block_q - 1) >= ik * block_k
    run = jnp.bool_(block_needed)
    if window is not None:
        # block fully below the window? q_pos - window >= k_pos for all pairs
        run = jnp.logical_and(
            run,
            (q_offset + iq * block_q - window) < (ik * block_k + block_k - 1),
        )

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]          # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)       # (bq, bk)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, D)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "scale", "block_q", "block_k", "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, K, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    assert H % K == 0, (H, K)
    G = H // K
    scale = scale if scale is not None else D ** -0.5

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k

    # head-major layout for clean 2D blocks
    qh = jnp.moveaxis(q, 2, 1)  # (B, H, Sq, D)
    kh = jnp.moveaxis(k, 2, 1)  # (B, K, Sk, D)
    vh = jnp.moveaxis(v, 2, 1)
    if pq:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // block_q
    nk = (Sk + pk) // block_k

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        seq_k=Sk,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)

    out = jnp.moveaxis(out, 1, 2)[:, :Sq]  # (B, Sq, H, D)
    return out
