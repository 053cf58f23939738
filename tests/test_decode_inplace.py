"""The decode step keeps the attention KV cache in place.

``model.decode_step`` carries each attention layer's stacked K/V (and slot
positions, or page pools) through the layer scan and writes only the new rows
at the layer's index.  These tests hold it to a per-layer loop written here:
slice each layer's cache out of the stack, run ``block_decode`` on that one
layer, restack — the scan-inputs/scan-outputs form the step had before.  Logits and every cache leaf must match bit for bit, across
the cache layouts and stacks that share the step: dense, ragged speculative
verify with rollback, sliding-window ring buffers past wrap-around, paged
pools, multi-layer scan blocks, hybrid SSM stacks and encoder-decoder stacks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.distributed.sharding import unzip_params
from repro.models import build_model
from repro.models import transformer as tfm
from repro.models.attention import cache_capacity
from repro.models.layers import embed_tokens, rms_norm, unembed

# the leaves attention writes at T positions per step
KV_LEAVES = ("k", "v", "kv_pos")


def _per_layer_decode(model, params, cache, tokens):
    """decode_step as a loop over blocks on sliced caches.

    The loop is a ``lax.scan`` whose inputs are each block's slice of every
    cache leaf and whose outputs restack them: each block's attention cache
    is handed to ``block_decode`` as a stack of one layer.  (A Python loop
    would be unrolled, and XLA then fuses across layers and rounds bf16
    differently, so it could only be compared with a tolerance.)
    """
    cfg = model.cfg
    blocks = cache["blocks"]
    kv = {n: {k: a for k, a in c.items() if k in KV_LEAVES}
          for n, c in blocks.items()}
    rest = {n: {k: a for k, a in c.items() if k not in KV_LEAVES}
            for n, c in blocks.items()}

    def body(carry, inp):
        x, aux = carry
        bp, kv_b, rest_b = inp
        one = jax.tree.map(lambda a: a[None], kv_b)
        x, aux, one, new_b = tfm.block_decode(
            bp, cfg, x, aux, one, rest_b, cache["len"], jnp.int32(0),
            mem_len=cache.get("mem_len"), block_tables=cache.get("bt"),
        )
        return (x, aux), (jax.tree.map(lambda a: a[0], one), new_b)

    x = embed_tokens(params["embedding"], tokens)
    (x, _), (kv, new) = jax.lax.scan(
        body, (x, dict(tfm.AUX0)), (params["blocks"], kv, rest)
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embedding"], x, cfg.tie_embeddings, cfg.vocab_size)
    out = dict(cache, blocks={n: {**blocks[n], **kv[n], **new[n]} for n in blocks})
    out["len"] = cache["len"] + tokens.shape[1]
    return logits.astype(jnp.float32), out


def _assert_same(got, want):
    g_leaves, _ = jax.tree.flatten_with_path(got)
    w_leaves, _ = jax.tree.flatten_with_path(want)
    assert [p for p, _ in g_leaves] == [p for p, _ in w_leaves]
    for (path, g), (_, w) in zip(g_leaves, w_leaves, strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path)
        )


def _tokens(rng, cfg, B, T):
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)


def _prefilled(cfg, rng, B, S, max_len, lengths=None):
    model = build_model(cfg)
    params, _ = unzip_params(model.init(jax.random.PRNGKey(7)))
    batch = {"tokens": _tokens(rng, cfg, B, S)}
    if lengths is not None:
        batch["lengths"] = jnp.asarray(lengths, jnp.int32)
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, 6, cfg.d_model)) * 0.1, jnp.dtype(cfg.dtype)
        )
    _, cache = model.prefill(params, batch, max_len=max_len)
    return model, params, cache


def _paged(cfg, rng, B=2, page=8, n_pages=24, max_context=64):
    """A paged cache with random pool contents, scrambled block tables and
    rows already partly filled (positions below ``len`` are live)."""
    model = build_model(cfg)
    params, _ = unzip_params(model.init(jax.random.PRNGKey(7)))
    cache = model.init_paged_cache(B, n_pages, page, max_context)
    key = jax.random.PRNGKey(3)
    blocks = jax.tree.map(
        lambda a: jax.random.normal(key, a.shape, jnp.float32).astype(a.dtype),
        cache["blocks"],
    )
    pages = rng.permutation(n_pages)[: B * (max_context // page)]
    bt = pages.reshape(B, -1).astype(np.int32)
    bt[1, -2:] = -1  # unallocated tail: writes there drop
    cache = dict(cache, blocks=blocks, bt=jnp.asarray(bt),
                 len=jnp.asarray([5, 30], jnp.int32))
    return model, params, cache


def _case(name, rng):
    """(model, params, cache, steps): each step is (T, accept_idx or None)."""
    qwen = reduced_config("qwen3-1.7b")
    if name == "dense_t1":
        return (*_prefilled(qwen, rng, 2, 12, 64), [(1, None), (1, None)])
    if name == "dense_t9_ragged_rollback":
        return (*_prefilled(qwen, rng, 3, 16, 64, lengths=[5, 16, 11]),
                [(9, [2, 0, 8]), (9, None)])
    if name == "swa_ring_wrap":
        cfg = reduced_config("h2o-danube-3-4b")
        assert cache_capacity(cfg, 64) == 48  # window 16 + margin 32
        # prefill 40, then three verify steps: positions 40..66 wrap the ring
        return (*_prefilled(cfg, rng, 2, 40, 64),
                [(9, [8, 3]), (9, [8, 8]), (9, None)])
    if name == "paged":
        return (*_paged(qwen, rng), [(9, [4, 8]), (9, None)])
    if name == "scan_block2":
        cfg = dataclasses.replace(qwen, scan_block=2)
        return (*_prefilled(cfg, rng, 2, 12, 64), [(9, [3, 6]), (1, None)])
    if name == "hybrid_ssm":
        cfg = reduced_config("jamba-1.5-large-398b")
        assert set(cfg.layer_kinds()) == {"attn", "ssm"} and cfg.scan_block > 1
        return (*_prefilled(cfg, rng, 2, 12, 64), [(9, [1, 5]), (9, None)])
    if name == "encdec":
        cfg = reduced_config("seamless-m4t-large-v2")
        assert cfg.is_encdec
        return (*_prefilled(cfg, rng, 2, 10, 64), [(9, [2, 7]), (1, None)])
    raise ValueError(name)


@pytest.mark.parametrize("name", [
    "dense_t1", "dense_t9_ragged_rollback", "swa_ring_wrap", "paged",
    "scan_block2", "hybrid_ssm", "encdec",
])
def test_decode_step_matches_per_layer_loop(name):
    rng = np.random.default_rng(11)
    model, params, cache, steps = _case(name, rng)
    scanned = jax.jit(model.decode_step)
    looped = jax.jit(lambda p, c, t: _per_layer_decode(model, p, c, t))
    commit = jax.jit(model.commit_cache)
    got = want = cache
    for T, accept in steps:
        tokens = _tokens(rng, model.cfg, cache["len"].shape[0], T)
        old_len = got["len"]
        g_logits, got = scanned(params, got, tokens)
        w_logits, want = looped(params, want, tokens)
        _assert_same((g_logits, got), (w_logits, want))
        if accept is not None:
            accept = jnp.asarray(accept, jnp.int32)
            got = commit(got, old_len, accept)
            want = commit(want, old_len, accept)
    # the steps wrote something: the attention cache moved off its start
    assert any(
        bool((got["blocks"][n][k] != a).any())
        for n, c in cache["blocks"].items() for k, a in c.items() if k in KV_LEAVES
    )
