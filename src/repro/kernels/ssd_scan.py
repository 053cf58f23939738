"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

Implements the chunked dual form of arXiv:2405.21060 §6: within a chunk of
``c`` tokens the recurrence is evaluated as a (masked, decay-weighted)
quadratic attention-like product — MXU-friendly; across chunks the
(H, P, N) recurrent state is propagated sequentially.

Tiling
------
Grid ``(B, H/hb, nc)`` — batch × head-block × chunk, the chunk axis
sequential ("arbitrary") so the running state lives in a ``(hb, P, N)``
float32 VMEM scratch carried across chunks.  Per grid step the kernel
computes, entirely in VMEM:

    dA   = dt * A                cumsum -> dA_cs          (hb, c)
    L    = exp(segsum(dA))       lower-triangular decay   (hb, c, c)
    CB   = C @ B^T               shared across the group  (c, c)
    y    = (CB ∘ L ∘ dt_j) @ x   intra-chunk term         (hb, c, P)
         + (C @ state^T) ∘ exp(dA_cs)   inter-chunk term
    state= state * exp(dA_cs[-1]) + (x ∘ dt ∘ decay_to_end)^T B

VMEM budget at (hb=8, c=256, P=64, N=128): x/y 512 KiB each, L 2 MiB,
CB 256 KiB, state 256 KiB — ~3.5 MiB, comfortably double-bufferable.
``c`` and ``N`` are multiples of 128 (MXU lanes); ``P=64`` rides the
sublane dimension.

All heads of a block must share one B/C group (``hb`` divides H/G); the
wrapper falls back to the chunked jnp reference otherwise.

Validated in ``interpret=True`` against ``ref.ssd_scan_naive`` in
tests/test_kernels.py (including initial-state and final-state paths).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    x_ref,      # (1, hb, c, P)
    dt_ref,     # (1, hb, c)
    a_ref,      # (hb, 1)
    b_ref,      # (1, 1, c, N)
    c_ref,      # (1, 1, c, N)
    s0_ref,     # (1, hb, P, N) initial state
    y_ref,      # (1, hb, c, P)
    sf_ref,     # (1, hb, P, N) final state
    state_ref,  # scratch (hb, P, N) f32
    *,
    chunk: int,
):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # (hb, c, P)
    dt = dt_ref[0].astype(jnp.float32)        # (hb, c)
    A = a_ref[...].astype(jnp.float32)        # (hb, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)      # (c, N)
    C = c_ref[0, 0].astype(jnp.float32)       # (c, N)
    hb = x.shape[0]

    dA = dt * A                                # (hb, c)
    dA_cs = jnp.cumsum(dA, axis=-1)            # inclusive
    # --- intra-chunk quadratic term ---------------------------------------
    seg = dA_cs[:, :, None] - dA_cs[:, None, :]          # (hb, c, c)
    ii = jax.lax.broadcasted_iota(jnp.int32, (hb, chunk, chunk), 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (hb, chunk, chunk), 2)
    L = jnp.exp(jnp.where(ii >= jj, seg, NEG_INF))       # causal decay
    CB = jax.lax.dot_general(
        C, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (c, c)
    M = CB[None] * L * dt[:, None, :]                    # weight column j by dt_j
    y = jax.lax.dot_general(
        M, x, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # (hb, c, P)
    # --- inter-chunk term (contribution of the carried state) -------------
    state = state_ref[...]                                # (hb, P, N)
    y_inter = jax.lax.dot_general(
        state, C, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (hb, P, c)
    y += y_inter.swapaxes(1, 2) * jnp.exp(dA_cs)[..., None]
    y_ref[0] = y.astype(y_ref.dtype)
    # --- state update ------------------------------------------------------
    chunk_decay = jnp.exp(dA_cs[:, -1])                   # (hb,)
    decay_to_end = jnp.exp(dA_cs[:, -1:] - dA_cs)         # (hb, c)
    xw = x * (dt * decay_to_end)[..., None]               # (hb, c, P)
    upd = jax.lax.dot_general(
        xw, Bm, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (hb, P, N)
    state_ref[...] = state * chunk_decay[:, None, None] + upd

    @pl.when(ic == nc - 1)
    def _finish():
        sf_ref[0] = state_ref[...].astype(sf_ref.dtype)


def _pick_head_block(rep: int) -> int:
    for hb in (8, 4, 2, 1):
        if rep % hb == 0:
            return hb
    return 1


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "return_state", "interpret"),
)
def ssd_scan_pallas(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)  already softplus'ed
    A: jax.Array,    # (H,) negative
    Bm: jax.Array,   # (B, S, G, N)
    C: jax.Array,    # (B, S, G, N)
    *,
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
    return_state: bool = False,
    interpret: bool = False,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    dtype = x.dtype

    hb = _pick_head_block(rep)
    chunk = min(chunk, max(S, 8))
    pad = (-S) % chunk
    nc = (S + pad) // chunk

    # head-major layouts
    xh = jnp.moveaxis(x, 2, 1)                  # (B, H, S, P)
    dth = jnp.moveaxis(dt, 2, 1)                # (B, H, S)
    bh = jnp.moveaxis(Bm, 2, 1)                 # (B, G, S, N)
    ch = jnp.moveaxis(C, 2, 1)
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dth = jnp.pad(dth, ((0, 0), (0, 0), (0, pad)))  # dt=0 -> no-op rows
        bh = jnp.pad(bh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ch = jnp.pad(ch, ((0, 0), (0, 0), (0, pad), (0, 0)))
    a2 = A.reshape(H, 1).astype(jnp.float32)
    s0 = (
        jnp.zeros((Bsz, H, P, N), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, sf = pl.pallas_call(
        kernel,
        grid=(Bsz, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, ih, ic: (b, ih, ic, 0)),
            pl.BlockSpec((1, hb, chunk), lambda b, ih, ic: (b, ih, ic)),
            pl.BlockSpec((hb, 1), lambda b, ih, ic: (ih, 0)),
            # all heads of a block share one group: g = (ih*hb)//rep
            pl.BlockSpec((1, 1, chunk, N), lambda b, ih, ic, _r=rep, _h=hb: (b, (ih * _h) // _r, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, ih, ic, _r=rep, _h=hb: (b, (ih * _h) // _r, ic, 0)),
            pl.BlockSpec((1, hb, P, N), lambda b, ih, ic: (b, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, ih, ic: (b, ih, ic, 0)),
            pl.BlockSpec((1, hb, P, N), lambda b, ih, ic: (b, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S + pad, P), dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(xh, dth, a2, bh, ch, s0)

    y = jnp.moveaxis(y, 1, 2)[:, :S]  # (B, S, H, P)
    if return_state:
        return y, sf
    return y
