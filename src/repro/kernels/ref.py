"""Pure-jnp reference oracles for every kernel.

These are not throwaway test code: on non-TPU backends (this CPU container,
and any GPU fallback) the model forward passes run THESE implementations, so
they are written memory-consciously — chunked online-softmax attention rather
than materialising (Sq, Sk) score matrices, and the chunked SSD scan rather
than a length-T sequential recurrence.  The Pallas kernels in this package are
checked against these oracles in interpret mode.

Conventions
-----------
q : (B, Sq, H, D)          k, v : (B, Sk, K, D)   (K = kv heads, H = K * G)
SSD x : (B, S, H, P)  dt : (B, S, H)  A : (H,)  Bm/C : (B, S, G, N)
All attention math accumulates in float32 regardless of input dtype.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Flash attention (prefill / training)
# ---------------------------------------------------------------------------


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, K, G, D), k: (B, Sk, K, D) -> (B, K, G, Sq, Sk), fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Chunked online-softmax attention with GQA, causal and SWA masking.

    ``q_offset`` is the absolute position of q[0] (used when the query block
    sits at the end of a longer KV, e.g. chunked prefill continuation).
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    assert H % K == 0, (H, K)
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    dtype = q.dtype

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to multiples
    pq = (-Sq) % q_chunk
    pk = (-Sk) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // q_chunk, (Sk + pk) // kv_chunk

    q = q.reshape(B, nq, q_chunk, K, G, D).astype(jnp.float32) * scale
    k = k.reshape(B, nk, kv_chunk, K, D)
    v = v.reshape(B, nk, kv_chunk, K, D)

    q_pos = q_offset + jnp.arange(Sq + pq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Sk + pk).reshape(nk, kv_chunk)
    k_valid = (jnp.arange(Sk + pk) < Sk).reshape(nk, kv_chunk)

    def q_body(_, inp):
        qi, qp = inp
        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)

        def inner(carry, kv_inp):
            m, l, acc = carry
            ki, vi, kp, kval = kv_inp
            s = _gqa_scores(qi, ki)
            mask = kval[None, :]
            if causal:
                mask = mask & (kp[None, :] <= qp[:, None])
            if window is not None:
                mask = mask & (kp[None, :] > qp[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vi, preferred_element_type=jnp.float32
            )
            return (m_new, l, acc), None

        inner = jax.checkpoint(inner, prevent_cse=False)
        (m, l, acc), _ = jax.lax.scan(
            inner,
            (m0, l0, a0),
            (k.swapaxes(0, 1), v.swapaxes(0, 1), k_pos, k_valid),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out  # (B, K, G, q_chunk, D)

    _, outs = jax.lax.scan(q_body, None, (q.swapaxes(0, 1), q_pos))
    # outs: (nq, B, K, G, q_chunk, D) -> (B, Sq, H, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, (Sq + pq), H, D)
    return out[:, :Sq].astype(dtype)


def attention_naive(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """O(Sq*Sk) dense attention — the oracle the chunked version is tested against."""
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qf = q.reshape(B, Sq, K, G, D).astype(jnp.float32) * scale
    s = _gqa_scores(qf, k)  # (B,K,G,Sq,Sk)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v, preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (single or few query tokens against a KV cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    causal: bool = True,
) -> jax.Array:
    """Attention of T new tokens against a (padded / ring-buffer) KV cache.

    q            : (B, T, H, D) — the T new tokens (T >= 1; speculative verify
                   passes T = depth+1)
    k/v_cache    : (B, S, K, D) — S is the cache capacity; positions >=
                   cache_len are masked.  For ring-buffer (SWA) caches pass
                   ``kv_positions`` with the absolute position of every slot.
    cache_len    : (B,) int32 — valid length (new tokens already written).
    The i-th query token has absolute position cache_len - T + i.
    ``causal=False`` (cross attention) lets every query see every valid slot.
    """
    B, T, H, D = q.shape
    _, S, K, _ = k_cache.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qf = q.reshape(B, T, K, G, D).astype(jnp.float32) * scale
    s = _gqa_scores(qf, k_cache)  # (B,K,G,T,S)

    q_pos = cache_len[:, None] - T + jnp.arange(T)[None, :]  # (B,T)
    if kv_positions is None:
        kv_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        valid = kv_pos < cache_len[:, None]
    else:
        kv_pos = kv_positions  # (B,S) absolute positions written into slots
        valid = kv_pos >= 0
    mask = jnp.broadcast_to(valid[:, None, :], (B, T, S))
    if causal:
        mask = mask & (kv_pos[:, None, :] <= q_pos[:, :, None])  # (B,T,S)
    if window is not None:
        mask &= kv_pos[:, None, :] > q_pos[:, :, None] - window
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v_cache, preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, D).astype(q.dtype)


def decode_attention_paged(
    q: jax.Array,          # (B, T, H, D)
    k_pages: jax.Array,    # (n_pages, K, ps, D) global head-major page pool
    v_pages: jax.Array,
    cache_len: jax.Array,  # (B,) valid length INCLUDING the T new tokens
    block_tables: jax.Array,  # (B, P) page indices into the pool, -1 = unset
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    causal: bool = True,
) -> jax.Array:
    """Block-table-indexed decode attention over a global page pool.

    Gathers each row's pages into a contiguous (B, P*ps) view and delegates
    to :func:`decode_attention`.  Slot ``s`` of row-page-index ``i`` holds
    absolute position ``i*ps + s`` by construction (positions are written
    exactly once in the paged layout — no ring wrap), so ``kv_positions`` is
    implicit; unallocated table entries (-1) mask their whole page.
    """
    n_pages, K, ps, D = k_pages.shape
    B, P = block_tables.shape
    pages = jnp.clip(block_tables, 0, n_pages - 1)

    def rows(pool):  # (B, P, K, ps, D) -> (B, P*ps, K, D)
        return pool[pages].transpose(0, 1, 3, 2, 4).reshape(B, P * ps, K, D)

    kv_pos = jnp.where(
        jnp.repeat(block_tables, ps, axis=1) >= 0,
        jnp.arange(P * ps, dtype=jnp.int32)[None, :],
        -1,
    )
    return decode_attention(
        q, rows(k_pages), rows(v_pages), cache_len, kv_positions=kv_pos,
        window=window, scale=scale, causal=causal,
    )


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — chunked scan
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k in (j, i]} x[..., k] (i >= j)."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(mask, out, NEG_INF)


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    C: jax.Array,
    *,
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,
    return_state: bool = False,
):
    """Chunked SSD forward (Mamba-2, arXiv:2405.21060 §6).

    x  : (B, S, H, P)    dt : (B, S, H)  (already softplus'ed)
    A  : (H,) negative   Bm, C : (B, S, G, N)
    Returns y : (B, S, H, P) (+ final state (B, H, P, N) if requested).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    dtype = x.dtype
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // chunk

    xf = x.reshape(Bsz, nc, chunk, H, P).astype(jnp.float32)
    dtf = dt.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Bf = Bm.reshape(Bsz, nc, chunk, G, N).astype(jnp.float32)
    Cf = C.reshape(Bsz, nc, chunk, G, N).astype(jnp.float32)

    dA = dtf * A.astype(jnp.float32)[None, None, None, :]        # (B,nc,c,H)
    dA_cs = jnp.cumsum(dA, axis=2)                                # inclusive
    # --- intra-chunk (quadratic within the chunk) --------------------------
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))                # (B,nc,H,c,c)
    CB = jnp.einsum("bucgn,busgn->bugcs", Cf, Bf)                 # (B,nc,G,c,c)
    CB = jnp.repeat(CB, rep, axis=2)                              # (B,nc,H,c,c)
    M = CB * L * dtf.transpose(0, 1, 3, 2)[:, :, :, None, :]      # weight dt_j
    y_intra = jnp.einsum("buhcs,bushp->buchp", M, xf)
    # --- chunk states -------------------------------------------------------
    decay_to_end = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)           # (B,nc,c,H)
    Bh = jnp.repeat(Bf, rep, axis=3)                              # (B,nc,c,H,N)
    states = jnp.einsum(
        "bushn,bushp->buhpn",
        Bh,
        xf * (dtf * decay_to_end)[..., None],
    )                                                             # (B,nc,H,P,N)
    # --- inter-chunk recurrence over chunk index ----------------------------
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])                     # (B,nc,H)

    def scan_fn(s_prev, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    s0 = (
        jnp.zeros((Bsz, H, P, N), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    s_final, s_before = jax.lax.scan(
        scan_fn, s0, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1))
    )
    s_before = s_before.swapaxes(0, 1)                            # (B,nc,H,P,N)
    # --- inter-chunk contribution -------------------------------------------
    Cr = jnp.repeat(Cf, rep, axis=3)                              # (B,nc,c,H,N)
    decay_in = jnp.exp(dA_cs)                                     # (B,nc,c,H)
    y_inter = jnp.einsum("buchn,buhpn->buchp", Cr * decay_in[..., None], s_before)

    y = (y_intra + y_inter).reshape(Bsz, S + pad, H, P)[:, :S].astype(dtype)
    if return_state:
        return y, s_final.astype(jnp.float32)
    return y


def ssd_scan_naive(x, dt, A, Bm, C, *, initial_state=None, return_state: bool = False):
    """Step-by-step recurrence — oracle for :func:`ssd_scan`."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    s = (
        jnp.zeros((Bsz, H, P, N), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)  # (B,S,H,N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)

    def step(s, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(dtt * A[None, :])
        s = s * decay[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xt * dtt[..., None], bt
        )
        y = jnp.einsum("bhpn,bhn->bhp", s, ct)
        return s, y

    s, ys = jax.lax.scan(
        step, s, (xf.swapaxes(0, 1), dtf.swapaxes(0, 1), Bf.swapaxes(0, 1), Cf.swapaxes(0, 1))
    )
    y = ys.swapaxes(0, 1).astype(x.dtype)
    if return_state:
        return y, s
    return y


def ssd_decode_step(
    state: jax.Array,
    x_t: jax.Array,
    dt_t: jax.Array,
    A: jax.Array,
    B_t: jax.Array,
    C_t: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD recurrence for decode.

    state : (B, H, P, N)   x_t : (B, H, P)   dt_t : (B, H)
    B_t, C_t : (B, G, N)
    Returns (new_state, y_t (B, H, P)).
    """
    H = x_t.shape[1]
    G = B_t.shape[1]
    rep = H // G
    Bh = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)
    Ch = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, :])
    new_state = state * decay[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", x_t.astype(jnp.float32) * dtf[..., None], Bh
    )
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch).astype(x_t.dtype)
    return new_state, y
