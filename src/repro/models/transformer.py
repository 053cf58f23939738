"""Block assembly: scan-over-blocks stacks for every architecture family.

A *block* is ``cfg.scan_block`` consecutive layers.  Blocks are required to be
structurally identical (asserted at init), are initialised under ``vmap`` so
their params carry a leading ``layer`` axis, and are applied under
``lax.scan`` — keeping compiled HLO size O(one block) regardless of depth
(72-layer Jamba compiles as one 8-layer block scanned 9 times).

Layer kinds come from ``cfg.layer_kinds()`` ("attn" / "ssm"); the MLP of each
layer is dense or MoE per ``cfg.moe_layer_mask()``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import P, constraint
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import apply_mlp, init_mlp, init_rms_norm, rms_norm

AUX0 = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}


def _block_pattern(cfg: ArchConfig) -> Tuple[Tuple[str, bool], ...]:
    """(kind, is_moe) per layer position within a block; validated periodic."""
    kinds = cfg.layer_kinds()
    moe_mask = cfg.moe_layer_mask()
    sb = cfg.scan_block
    assert cfg.n_layers % sb == 0, (cfg.n_layers, sb)
    pattern = tuple((kinds[i], moe_mask[i]) for i in range(sb))
    for b in range(cfg.n_layers // sb):
        got = tuple((kinds[b * sb + i], moe_mask[b * sb + i]) for i in range(sb))
        assert got == pattern, f"blocks not homogeneous: block {b} {got} != {pattern}"
    return pattern


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ArchConfig, cross: bool = False) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    pattern = _block_pattern(cfg)
    block: Dict[str, Any] = {}
    keys = jax.random.split(key, len(pattern) * 4)
    for i, (kind, is_moe) in enumerate(pattern):
        k0, k1, k2, k3 = keys[4 * i : 4 * i + 4]
        layer: Dict[str, Any] = {"norm1": init_rms_norm(cfg.d_model, dtype)}
        if kind == "attn":
            layer["attn"] = attn.init_attention(k0, cfg)
        else:
            layer["mamba"] = ssm.init_mamba(k0, cfg)
        if cross:  # decoder layers of an enc-dec model
            layer["norm_cross"] = init_rms_norm(cfg.d_model, dtype)
            layer["cross"] = attn.init_attention(k1, cfg, cross=True)
        if is_moe:
            layer["norm2"] = init_rms_norm(cfg.d_model, dtype)
            layer["moe"] = moe_mod.init_moe(k2, cfg)
        elif cfg.d_ff > 0:
            layer["norm2"] = init_rms_norm(cfg.d_model, dtype)
            layer["mlp"] = init_mlp(k3, cfg, cfg.d_ff)
        block[str(i)] = layer
    return block


def init_block_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Dict[str, Any]:
    pattern = _block_pattern(cfg)
    cache: Dict[str, Any] = {}
    for i, (kind, _) in enumerate(pattern):
        if kind == "attn":
            cache[str(i)] = attn.init_decode_cache(cfg, batch, max_len, dtype)
        else:
            cache[str(i)] = ssm.init_mamba_cache(cfg, batch, dtype)
    return cache


def init_block_page_pool(cfg: ArchConfig, n_pages: int, page_size: int, dtype) -> Dict[str, Any]:
    """Per-layer global page pools (paged decode; attention-only stacks —
    SSM state is not positional, so it cannot live in pages)."""
    pattern = _block_pattern(cfg)
    assert all(kind == "attn" for kind, _ in pattern), \
        "paged KV requires an attention-only stack"
    return {
        str(i): attn.init_page_pool(cfg, n_pages, page_size, dtype)
        for i in range(len(pattern))
    }


# ---------------------------------------------------------------------------
# Block apply (three modes share one layer walker)
# ---------------------------------------------------------------------------


def _apply_ffn(layer: Dict[str, Any], cfg: ArchConfig, x: jax.Array, aux: Dict) -> Tuple[jax.Array, Dict]:
    if "moe" in layer:
        h, losses = moe_mod.apply_moe(layer["moe"], cfg, rms_norm(x, layer["norm2"], cfg.norm_eps))
        aux = {k: aux[k] + losses[k] for k in aux}
        return x + h, aux
    if "mlp" in layer:
        h = apply_mlp(layer["mlp"], cfg, rms_norm(x, layer["norm2"], cfg.norm_eps))
        return x + h, aux
    return x, aux


def block_full(
    params: Dict[str, Any],
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    aux: Dict,
    *,
    causal: bool = True,
    cross_mem: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Dict]:
    """Full-sequence (training / encoder) pass through one block.

    ``cross_mem`` = (enc_out, mem_len): each decoder layer projects the
    encoder output through its OWN cross K/V weights.
    """
    for i in range(cfg.scan_block):
        layer = params[str(i)]
        h = rms_norm(x, layer["norm1"], cfg.norm_eps)
        if "attn" in layer:
            x = x + attn.attention_full(layer["attn"], cfg, h, positions, causal=causal)
        else:
            x = x + ssm.mamba_full(layer["mamba"], cfg, h)
        if cross_mem is not None:
            hc = rms_norm(x, layer["norm_cross"], cfg.norm_eps)
            enc_out, mlen = cross_mem
            mk, mv = attn.cross_memory(layer["cross"], cfg, enc_out)
            x = x + attn.attention_cross(layer["cross"], cfg, hc, mk, mv, mlen)
        x, aux = _apply_ffn(layer, cfg, x, aux)
        x = constraint(x, ("batch", None, "embed"))
    return x, aux


def block_prefill(
    params: Dict[str, Any],
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    aux: Dict,
    cache: Dict[str, Any],
    *,
    cross_mem: Optional[Tuple[jax.Array, jax.Array]] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, Dict[str, Any]]:
    """Prefill pass seeding the decode cache (incl. per-layer cross memories).

    ``lengths`` (B,) enables bucketed prefill: ``x`` is right-padded to a
    shape bucket and only the first ``lengths[b]`` positions of row ``b`` are
    real.  Causal attention already makes real positions independent of the
    trailing padding; the cache is seeded through the gather-based
    ``prefill_fill_cache`` (every row's full length when unbucketed; a ring
    buffer smaller than the prompt keeps the tail) so padded slots stay
    invisible (``kv_pos = -1``).
    Attention-only stacks only — SSM recurrent state cannot ignore a padded
    suffix, so callers gate bucketing on the architecture.
    """
    B, S = x.shape[:2]
    lens = lengths if lengths is not None else jnp.full((B,), S, jnp.int32)
    new_cache: Dict[str, Any] = {}
    for i in range(cfg.scan_block):
        layer = params[str(i)]
        h = rms_norm(x, layer["norm1"], cfg.norm_eps)
        if "attn" in layer:
            out, (k, v) = attn.attention_prefill(layer["attn"], cfg, h, positions)
            x = x + out
            c = cache[str(i)]
            ck, cv, cp = attn.prefill_fill_cache(k, v, lens, c["k"].shape[1], c["k"].dtype)
            nc = {"k": ck, "v": cv, "kv_pos": cp}
        else:
            if lengths is not None:
                raise NotImplementedError(
                    "bucketed (length-padded) prefill requires an attention-only "
                    "stack; SSM state would absorb the padding"
                )
            out, nc = ssm.mamba_prefill(layer["mamba"], cfg, h)
            x = x + out
        if cross_mem is not None:
            hc = rms_norm(x, layer["norm_cross"], cfg.norm_eps)
            enc_out, mlen = cross_mem
            mk, mv = attn.cross_memory(layer["cross"], cfg, enc_out)
            x = x + attn.attention_cross(layer["cross"], cfg, hc, mk, mv, mlen)
            nc = dict(nc, cross_k=mk, cross_v=mv)
        new_cache[str(i)] = nc
        x, aux = _apply_ffn(layer, cfg, x, aux)
        x = constraint(x, ("batch", None, "embed"))
    return x, aux, new_cache


def block_decode(
    params: Dict[str, Any],
    cfg: ArchConfig,
    x: jax.Array,
    aux: Dict,
    kv: Dict[str, Any],
    cache: Dict[str, Any],
    cache_len: jax.Array,
    block_idx: jax.Array,
    *,
    mem_len: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, Dict[str, Any], Dict[str, Any]]:
    """Decode T tokens through block ``block_idx`` of the stack.

    ``kv`` holds each attention layer position's KV cache stacked over all
    blocks (leading axis n_blocks): {"k", "v", "kv_pos"}, or with
    ``block_tables`` the global page pools {"k", "v"}.  The layer writes its
    T new rows into it in place at ``block_idx`` and reads its own slice for
    attention.  ``cache`` holds this block's other per-layer state: SSM
    ``conv``/``state`` and the cross memories (enc-dec, "cross_k"/"cross_v"),
    precomputed at prefill, with ``mem_len`` their valid length.

    Returns (x, aux, kv', new) — ``new`` holds each position's rewritten
    per-layer state (SSM state and its per-position rollback copies); the
    read-only cross memories are not re-emitted.
    """
    kv = dict(kv)
    new_cache: Dict[str, Any] = {}
    for i in range(cfg.scan_block):
        layer = params[str(i)]
        h = rms_norm(x, layer["norm1"], cfg.norm_eps)
        c = cache[str(i)]
        nc: Dict[str, Any] = {}
        if "attn" in layer:
            out, kv[str(i)] = attn.attention_decode(
                layer["attn"], cfg, h, kv[str(i)], cache_len, block_idx,
                block_tables=block_tables,
            )
        else:
            out, nc = ssm.mamba_decode(
                layer["mamba"], cfg, h, {k: c[k] for k in ("conv", "state")}
            )
        x = x + out
        if "cross" in layer:
            hc = rms_norm(x, layer["norm_cross"], cfg.norm_eps)
            x = x + attn.attention_cross(
                layer["cross"], cfg, hc, c["cross_k"], c["cross_v"], mem_len
            )
        new_cache[str(i)] = nc
        x, aux = _apply_ffn(layer, cfg, x, aux)
    return x, aux, kv, new_cache


def commit_block_cache(cache: Dict[str, Any], accept_idx: jax.Array) -> Dict[str, Any]:
    """Roll a block cache back to the accepted position (stacked over blocks)."""
    out: Dict[str, Any] = {}
    for key, c in cache.items():
        if "states_all" in c:
            # leaves carry a leading n_blocks axis -> vmap the per-layer commit
            out[key] = jax.vmap(ssm.commit_mamba, in_axes=(0, None))(c, accept_idx)
        else:
            out[key] = c
    return out


# ---------------------------------------------------------------------------
# Stacks: vmapped init + scanned apply
# ---------------------------------------------------------------------------


def init_stack(key, cfg: ArchConfig, n_blocks: int, cross: bool = False):
    keys = jax.random.split(key, n_blocks)
    stacked = jax.vmap(lambda k: init_block(k, cfg, cross=cross))(keys)
    # re-tag logical axes with the leading "layer" axis
    def retag(p: P) -> P:
        return P(p.value, ("layer",) + tuple(p.axes))

    return jax.tree.map(retag, stacked, is_leaf=lambda x: isinstance(x, P))


def _remat(fn: Callable, policy: str) -> Callable:
    if policy == "none":
        return fn
    if policy == "minimal":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save nothing


def scan_full(stacked, cfg: ArchConfig, x, positions, *, causal=True, cross_mem=None, remat="none"):
    def body(carry, bp):
        x, aux = carry
        x, aux = block_full(bp, cfg, x, positions, aux, causal=causal, cross_mem=cross_mem)
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(_remat(body, remat), (x, dict(AUX0)), stacked)
    return x, aux


def scan_prefill(stacked, cfg: ArchConfig, x, positions, cache, *, cross_mem=None,
                 lengths=None):
    def body(carry, inp):
        x, aux = carry
        bp, bc = inp
        x, aux, nc = block_prefill(
            bp, cfg, x, positions, aux, bc, cross_mem=cross_mem, lengths=lengths
        )
        return (x, aux), nc

    (x, aux), new_cache = jax.lax.scan(body, (x, dict(AUX0)), (stacked, cache))
    return x, aux, new_cache


# Leaves of a layer's cache that decode writes at T positions per step: the
# attention KV rows (or page pools) and their slot positions.
_IN_PLACE_LEAVES = ("k", "v", "kv_pos")


def scan_decode(stacked, cfg: ArchConfig, x, cache, cache_len, *, mem_len=None,
                block_tables=None):
    """Decode T tokens through every block of the stack.

    The cache is split by what the step does to each leaf.  Leaves written at
    T positions (``_IN_PLACE_LEAVES``: dense K/V rows and slot positions, or
    the page pools) ride in the scan carry whole, so each layer scatters its
    new rows into the one stacked buffer at its block index: no per-layer
    slab is re-stacked as a scan output and no second cache-sized buffer
    exists.  Every other leaf — SSM ``conv``/``state`` (rewritten whole,
    small) and the read-only cross memories — is scanned as a per-block
    slice; only the rewritten SSM state comes back as stacked outputs.  What
    is not in place: each attention layer still reads its layer out of the
    stack as a slice for the decode kernel (and the dense kernel transposes
    it head-major).
    """
    kv = {n: {k: a for k, a in c.items() if k in _IN_PLACE_LEAVES}
          for n, c in cache.items()}
    per_block = {n: {k: a for k, a in c.items() if k not in _IN_PLACE_LEAVES}
                 for n, c in cache.items()}

    def body(carry, inp):
        x, aux, kv = carry
        idx, bp, bc = inp
        x, aux, kv, nc = block_decode(bp, cfg, x, aux, kv, bc, cache_len, idx,
                                      mem_len=mem_len, block_tables=block_tables)
        return (x, aux, kv), nc

    n_blocks = cfg.n_layers // cfg.scan_block
    (x, _, kv), rewritten = jax.lax.scan(
        body, (x, dict(AUX0), kv), (jnp.arange(n_blocks), stacked, per_block)
    )
    return x, {n: {**cache[n], **kv[n], **rewritten[n]} for n in cache}
