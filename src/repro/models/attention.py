"""GQA attention with RoPE, qk-norm, QKV bias, sliding windows and KV caches.

Cache layout (per attention layer)
----------------------------------
``k``/``v`` : (B, cap, K, D) — ``cap`` is ``min(max_len, window + SPEC_MARGIN)``
for SWA archs (ring buffer) else ``max_len``.
``kv_pos``  : (B, cap) int32 — absolute position written into each slot, -1 if
empty.  Ring-buffer slots are addressed ``pos % cap``; the margin keeps
speculative (uncommitted) writes from clobbering live window entries before a
rollback.

The model stacks each layer position's cache over the blocks of its stack
(leading axis L); decode writes its new rows into that stack in place at the
layer's index (``write_cache`` / ``write_pages``).

Speculative rollback: rejected tokens simply leave stale slots behind; masking
is positional (slot position <= query position), so a rewound ``cache_len``
makes stale slots unreachable and they are overwritten on the next write.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import P, constraint
from repro.kernels import ops
from repro.models.layers import dense_init, rms_norm, rope

SPEC_MARGIN = 32  # ring-buffer slack for uncommitted speculative tokens


def cache_capacity(cfg: ArchConfig, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(max_len, cfg.sliding_window + SPEC_MARGIN)
    return max_len


def head_mask(cfg: ArchConfig, dtype) -> Optional[jax.Array]:
    """(H_pad,) 1.0 for real heads, 0.0 for TP-padding heads (or None)."""
    Hp, H, K = cfg.padded_heads, cfg.n_heads, cfg.n_kv_heads
    if Hp == H:
        return None
    G = H // K
    r = jnp.arange(Hp) % cfg.padded_group
    return (r < G).astype(dtype)


def init_attention(key, cfg: ArchConfig, cross: bool = False) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d, H, K, D = cfg.d_model, cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (H, D), ("embed", "heads", None), dtype),
        "wk": dense_init(ks[1], d, (K, D), ("embed", "kv", None), dtype),
        "wv": dense_init(ks[2], d, (K, D), ("embed", "kv", None), dtype),
        "wo": P(
            dense_init(ks[3], H * D, d, (None,), dtype).value.reshape(H, D, d),
            ("heads", None, "embed"),
        ),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = P(jnp.zeros((H, D), dtype), ("heads", None))
        p["bk"] = P(jnp.zeros((K, D), dtype), ("kv", None))
        p["bv"] = P(jnp.zeros((K, D), dtype), ("kv", None))
    if cfg.qk_norm and not cross:
        p["q_norm"] = P(jnp.ones((D,), dtype), (None,))
        p["k_norm"] = P(jnp.ones((D,), dtype), (None,))
    return p


def _project_q(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    return q


def _project_out(p: dict, cfg: ArchConfig, out: jax.Array, eq: str) -> jax.Array:
    """Output projection, masking TP-padding heads first so padded heads
    contribute nothing in forward or backward (their wq/wo grads are zero)."""
    hm = head_mask(cfg, out.dtype)
    if hm is not None:
        out = out * hm[None, None, :, None]
    return jnp.einsum(eq, out, p["wo"])


def _project_kv(p: dict, cfg: ArchConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    if "k_norm" in p:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def attention_full(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
) -> jax.Array:
    """Training / encoder forward over a full sequence."""
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = constraint(q, ("batch", None, "heads", None))
    k = constraint(k, ("batch", None, "kv", None))
    out = ops.flash_attention(
        q, k, v, causal=causal, window=cfg.sliding_window if causal else None
    )
    return _project_out(p, cfg, out, "bshe,hed->bsd")


def attention_prefill(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Prefill: causal attention returning (output, (k, v)) for cache seeding."""
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = constraint(q, ("batch", None, "heads", None))
    out = ops.flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
    return _project_out(p, cfg, out, "bshe,hed->bsd"), (k, v)


def write_cache(
    cache_k: jax.Array,
    cache_v: jax.Array,
    kv_pos: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    start_pos: jax.Array,
    layer: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Write layer ``layer``'s T new KV entries at absolute positions
    start_pos + [0, T), in place in the stacked cache.

    cache_k/v: (L, B, cap, K, D) and kv_pos: (L, B, cap) hold every layer of
    the stack; k/v_new: (B, T, K, D); start_pos: (B,); layer: scalar index.
    One scatter at ``[layer, row, slot]`` per leaf, slots ``position % cap``
    (ring buffer) — the rest of the stack is untouched.
    """
    cap = cache_k.shape[2]
    B, T = k_new.shape[:2]
    pos = start_pos[:, None] + jnp.arange(T)[None, :]  # (B, T)
    slots = (pos % cap).astype(jnp.int32)
    rows = jnp.arange(B)[:, None]
    return (
        cache_k.at[layer, rows, slots].set(k_new),
        cache_v.at[layer, rows, slots].set(v_new),
        kv_pos.at[layer, rows, slots].set(pos),
    )


def prefill_fill_cache(
    k_new: jax.Array,
    v_new: jax.Array,
    lengths: jax.Array,
    cap: int,
    dtype,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build a decode cache from prefill K/V (right-padded when bucketed).

    ``k_new``/``v_new``: (B, S, K, D) over the (padded) sequence; ``lengths``
    (B,) gives each row's real prompt length.  For cache slot ``j`` the winner
    is the LAST real position ``p < lengths`` with ``p % cap == j`` (ring
    semantics, gather-based so per-row variable lengths never produce
    conflicting scatter writes).  Padded positions never reach the cache:
    their slots keep ``kv_pos = -1``, so the positional decode mask makes
    bucketed prefill bit-invisible to every later decode step.
    """
    B, S = k_new.shape[:2]
    j = jnp.arange(cap)[None, :]                       # (1, cap)
    wrap = (lengths[:, None] - 1 - j) // cap           # (B, cap); < 0 => empty
    pos_win = j + cap * jnp.maximum(wrap, 0)
    valid = wrap >= 0
    idx = jnp.clip(pos_win, 0, S - 1)
    gk = jnp.take_along_axis(k_new, idx[..., None, None], axis=1)
    gv = jnp.take_along_axis(v_new, idx[..., None, None], axis=1)
    m = valid[..., None, None]
    return (
        jnp.where(m, gk, 0).astype(dtype),
        jnp.where(m, gv, 0).astype(dtype),
        jnp.where(valid, pos_win, -1).astype(jnp.int32),
    )


def _cp_mesh():
    """Mesh for context-parallel decode, if one is active with a model axis."""
    from repro.distributed.sharding import _current_mesh

    mesh = _current_mesh()
    if mesh is not None and "model" in mesh.axis_names and mesh.shape["model"] > 1:
        return mesh
    return None


def _decode_attention_cp(
    mesh, cfg: ArchConfig, q, k_new, v_new, cache, cache_len,
) -> Tuple[jax.Array, dict]:
    """Context-parallel decode attention (shard_map; beyond-paper perf path).

    The KV cache is sequence-sharded over the model axis; GSPMD's default
    lowering of softmax-over-sharded-S ALL-GATHERS the cache every step
    (3.6 GB/step/device at qwen2.5-14b decode_32k — dry-run measured).
    Here every shard instead (1) writes the new KV tokens locally iff the
    ring slot falls in its range, (2) computes flash-decode partial stats
    over its LOCAL slice, (3) merges with one psum of the (B,H,T,D)-sized
    numerator + (B,H,T) stats — ~0.4 MB vs 3.6 GB of collective traffic.
    """
    from jax.sharding import PartitionSpec as PS

    B, T, H, D = q.shape
    K = cfg.n_kv_heads
    batch_axes = tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    n_model = mesh.shape["model"]
    n_batch = 1
    for ax in batch_axes:
        n_batch *= mesh.shape[ax]
    cap = cache["k"].shape[1]
    if cap % n_model or B % n_batch:
        # indivisible capacity or batch (e.g. long_500k batch=1): fall back
        # to the GSPMD path, which replicates the batch dim instead
        return None
    S_loc = cap // n_model
    scale = D ** -0.5

    def body(q_l, kn, vn, ck, cv, cp, clen):
        j = jax.lax.axis_index("model")
        lo = j * S_loc
        Bl = q_l.shape[0]
        # ---- local ring-buffer write ------------------------------------
        pos = clen[:, None] + jnp.arange(T)[None, :]            # (Bl, T)
        slot = (pos % cap).astype(jnp.int32)
        local = (slot >= lo) & (slot < lo + S_loc)
        ls = jnp.clip(slot - lo, 0, S_loc - 1)

        def wr(ck1, cv1, cp1, kn1, vn1, ls1, loc1, pos1):
            old_k = ck1[ls1]
            old_v = cv1[ls1]
            old_p = cp1[ls1]
            m = loc1[:, None, None]
            ck1 = ck1.at[ls1].set(jnp.where(m, kn1, old_k))
            cv1 = cv1.at[ls1].set(jnp.where(m, vn1, old_v))
            cp1 = cp1.at[ls1].set(jnp.where(loc1, pos1, old_p))
            return ck1, cv1, cp1

        ck, cv, cp = jax.vmap(wr)(ck, cv, cp, kn, vn, ls, local, pos)
        # ---- local partial flash-decode ----------------------------------
        G = H // K
        qf = q_l.reshape(Bl, T, K, G, D).astype(jnp.float32) * scale
        s = jnp.einsum("btkgd,bskd->bkgts", qf, ck.astype(jnp.float32))
        q_pos = clen[:, None] + jnp.arange(T)[None, :]          # (Bl, T)
        mask = (cp[:, None, :] >= 0) & (cp[:, None, :] <= q_pos[:, :, None])
        if cfg.sliding_window is not None:
            mask &= cp[:, None, :] > q_pos[:, :, None] - cfg.sliding_window
        s = jnp.where(mask[:, None, None], s, -1e30)
        m = s.max(axis=-1)
        p_ = jnp.exp(s - m[..., None])
        l = p_.sum(axis=-1)
        num = jnp.einsum("bkgts,bskd->bkgtd", p_, cv.astype(jnp.float32))
        # ---- LSE merge across sequence shards ----------------------------
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        num_g = jax.lax.psum(num * corr[..., None], "model")
        l_g = jax.lax.psum(l * corr, "model")
        out = num_g / jnp.maximum(l_g, 1e-30)[..., None]        # (Bl,K,G,T,D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(Bl, T, H, D)
        return out.astype(q_l.dtype), ck, cv, cp

    qspec = PS(bspec, None, None, None)
    kvspec = PS(bspec, "model", None, None)
    out, ck, cv, cp = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, kvspec, kvspec, PS(bspec, "model"), PS(bspec)),
        out_specs=(qspec, kvspec, kvspec, PS(bspec, "model")),
        check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], cache["kv_pos"], cache_len)
    return out, {"k": ck, "v": cv, "kv_pos": cp}


def write_pages(
    pool_k: jax.Array,
    pool_v: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    block_tables: jax.Array,
    start_pos: jax.Array,
    layer: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter layer ``layer``'s T new KV entries into the stacked page pools
    via block tables, in place.

    pool_k/v: (L, n_pages, K, ps, D) — every layer's pool; head-major, so one
    head of one page is a contiguous (ps, D) tile for the paged decode
    kernel; k/v_new: (B, T, K, D); block_tables: (B, P) page indices (-1 =
    unallocated); start_pos: (B,).  Position ``p`` of row ``b`` lands in slot
    ``p % ps`` of page ``block_tables[b, p // ps]`` of layer ``layer`` —
    positions are written exactly once (no ring wrap; the block table is
    sized for the full context), so the paged decode mask can reconstruct
    positions from page indices alone.  Writes whose page entry is missing
    (or beyond the table) drop: inactive rows and bucket padding never touch
    live pages.
    """
    _, n_pages, K, ps, D = pool_k.shape
    B, T = k_new.shape[:2]
    P = block_tables.shape[1]
    pos = start_pos[:, None] + jnp.arange(T)[None, :]          # (B, T)
    pidx = pos // ps
    page = jnp.take_along_axis(block_tables, jnp.clip(pidx, 0, P - 1), axis=1)
    page = jnp.where((pidx < P) & (page >= 0), page, n_pages)  # OOB drops
    # one index per (row, token, head): the scatter window is a single
    # contiguous D vector, so the pool keeps its layout through the update
    page = page.reshape(B * T, 1)
    slot = (pos % ps).reshape(B * T, 1)
    head = jnp.arange(K)[None, :]
    kf = pool_k.at[layer, page, head, slot].set(
        k_new.reshape(B * T, K, D).astype(pool_k.dtype), mode="drop"
    )
    vf = pool_v.at[layer, page, head, slot].set(
        v_new.reshape(B * T, K, D).astype(pool_v.dtype), mode="drop"
    )
    return kf, vf


def attention_decode(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    cache: dict,
    cache_len: jax.Array,
    layer: jax.Array,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """Decode T new tokens (T >= 1 for speculative verification).

    ``cache`` = {"k", "v", "kv_pos"} stacked over the layers of the stack,
    (L, B, cap, ...); this call is layer ``layer``.  ``cache_len`` (B,) is the
    committed length BEFORE these tokens; query i sits at absolute position
    cache_len + i.  With ``block_tables`` the cache is instead the stacked
    page pools {"k", "v"}: (L, n_pages, K, ps, D) — writes and attention go
    through the per-row tables (paged layout; requires full attention, the
    engine gates SWA off).  The T new rows are scattered into the stacked
    buffers in place; the kernel then reads layer ``layer`` as a slice of
    them.  Returns (output, updated stacked cache).
    """
    B, T, _ = x.shape
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x)
    pos = cache_len[:, None] + jnp.arange(T)[None, :]
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    def view(a):
        return jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)

    if block_tables is not None:
        ck, cv = write_pages(cache["k"], cache["v"], k, v, block_tables,
                             cache_len, layer)
        out = ops.decode_attention_paged(
            q, view(ck), view(cv), cache_len + T, block_tables,
            window=cfg.sliding_window,
        )
        out = _project_out(p, cfg, out, "bthe,hed->btd")
        return out, {"k": ck, "v": cv}

    # context-parallel path: sequence-sharded KV, LSE-merged (see
    # _decode_attention_cp); ring-buffer (SWA) caches shard the same way,
    # with the window folded into the position mask.  It works on the
    # layer's slice and writes that slice back into the stack.
    mesh = _cp_mesh()
    if mesh is not None:
        res = _decode_attention_cp(
            mesh, cfg, q, k, v, {n: view(a) for n, a in cache.items()}, cache_len
        )
        if res is not None:
            out, new_layer = res
            out = _project_out(p, cfg, out, "bthe,hed->btd")
            return out, {
                n: jax.lax.dynamic_update_index_in_dim(a, new_layer[n], layer, 0)
                for n, a in cache.items()
            }

    ck, cv, cp = write_cache(cache["k"], cache["v"], cache["kv_pos"], k, v,
                             cache_len, layer)
    out = ops.decode_attention(
        q,
        constraint(view(ck), ("batch", "kv_seq", "kv", None)),
        constraint(view(cv), ("batch", "kv_seq", "kv", None)),
        cache_len + T,
        kv_positions=view(cp),
        window=cfg.sliding_window,
    )
    out = _project_out(p, cfg, out, "bthe,hed->btd")
    return out, {"k": ck, "v": cv, "kv_pos": cp}


def attention_cross(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    mem_k: jax.Array,
    mem_v: jax.Array,
    mem_len: jax.Array,
) -> jax.Array:
    """Cross attention against precomputed encoder memory (no RoPE, no mask
    beyond source-length validity)."""
    q = _project_q(p, cfg, x)
    out = ops.decode_attention(q, mem_k, mem_v, mem_len, window=None, causal=False)
    return _project_out(p, cfg, out, "bthe,hed->btd")


def cross_memory(p: dict, cfg: ArchConfig, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder output (prefill-time)."""
    return _project_kv(p, cfg, enc_out)


def init_decode_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    cap = cache_capacity(cfg, max_len)
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, cap, K, D), dtype),
        "v": jnp.zeros((batch, cap, K, D), dtype),
        "kv_pos": jnp.full((batch, cap), -1, jnp.int32),
    }


def init_page_pool(cfg: ArchConfig, n_pages: int, page_size: int, dtype) -> dict:
    """Global paged KV pool shared by all decode slots (one per attn layer),
    head-major: (n_pages, K, page_size, D)."""
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((n_pages, K, page_size, D), dtype),
        "v": jnp.zeros((n_pages, K, page_size, D), dtype),
    }
