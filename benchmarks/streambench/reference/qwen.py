"""Plain float32 forward pass of the Qwen2 and Qwen3 decoders (the ``qwen`` family).

It follows the published modelling code (Hugging Face ``modeling_qwen2`` and
``modeling_qwen3``): pre-norm RMSNorm blocks, rotary embeddings on the two
halves of each head, grouped-query attention with a causal mask, a SwiGLU
MLP, and a final RMSNorm before the (tied or separate) output head.  Qwen3
normalises each query and key head (``q_norm``, ``k_norm``) before the
rotation; Qwen2 adds a bias to the q, k and v projections.

It imports nothing of the program under test.  Weights come in the layout of
``families/qwen.py``: matrices ``(in, out)``, layers stacked on the first
axis.  Every matrix product runs at ``Precision.HIGHEST``, so a TPU computes
it in float32.  One sequence at a time, layer by layer, so that it fits
beside the weights after the program has been freed.

``quant="int8"`` is the control: each projection runs on int8 values
(weights per output channel, activations per token, symmetric), which is the
step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int
    H: int
    K: int
    D: int
    V: int
    tied: bool
    qk_norm: bool
    qkv_bias: bool
    eps: float
    theta: float


def dims(cfg: Dict[str, Any]) -> Dims:
    family = cfg["model_type"]
    if family not in ("qwen2", "qwen3"):
        raise ValueError(f"the reference covers qwen2 and qwen3, not {family!r}")
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return Dims(
        d=d, H=H, K=int(cfg["num_key_value_heads"]),
        D=int(cfg.get("head_dim") or d // H), V=int(cfg["vocab_size"]),
        tied=bool(cfg["tie_word_embeddings"]), qk_norm=family == "qwen3",
        qkv_bias=family == "qwen2" or bool(cfg.get("attention_bias", False)),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
    )


def _fake_int8(a: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _lin(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x (L, heads, D); rotate_half convention of the published code."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) * 2 / x.shape[-1]))
    ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _layer(m: Dims, quant: Optional[str], x: jax.Array, p: Dict[str, jax.Array]):
    L = x.shape[0]
    pos = jnp.arange(L)
    h = _rms(x, p["ln1"], m.eps)
    q, k, v = _lin(h, p["wq"], quant), _lin(h, p["wk"], quant), _lin(h, p["wv"], quant)
    if m.qkv_bias:
        q = q + p["bq"].astype(jnp.float32)
        k = k + p["bk"].astype(jnp.float32)
        v = v + p["bv"].astype(jnp.float32)
    q, k, v = (q.reshape(L, m.H, m.D), k.reshape(L, m.K, m.D), v.reshape(L, m.K, m.D))
    if m.qk_norm:
        q, k = _rms(q, p["q_norm"], m.eps), _rms(k, p["k_norm"], m.eps)
    q, k = _rope(q, pos, m.theta), _rope(k, pos, m.theta)
    G = m.H // m.K
    qg = q.reshape(L, m.K, G, m.D)              # head h reads kv head h // G
    s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) * m.D ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", a, v, precision=HIGHEST).reshape(L, m.H * m.D)
    x = x + _lin(o, p["wo"], quant)
    h = _rms(x, p["ln2"], m.eps)
    g = _lin(h, p["gate"], quant)
    x = x + _lin(jax.nn.silu(g) * _lin(h, p["up"], quant), p["down"], quant)
    return x, None


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _logits_at(m: Dims, quant: Optional[str], n_pos: int, vocab_chunks: int,
               w: Dict[str, Any], tokens: jax.Array, start: jax.Array) -> jax.Array:
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, m, quant), x, w["layers"])
    x = _rms(x, w["norm"], m.eps)
    sel = jax.lax.dynamic_slice_in_dim(x, start, n_pos, axis=0)
    head = w["embed"] if m.tied else w["head"]
    axis = 0 if m.tied else 1
    c = m.V // vocab_chunks

    def chunk(i):
        h = jax.lax.dynamic_slice_in_dim(head, i * c, c, axis=axis)
        return _lin(sel, h.T if m.tied else h, quant)

    out = jax.lax.map(chunk, jnp.arange(vocab_chunks))     # (chunks, n_pos, c)
    return out.transpose(1, 0, 2).reshape(n_pos, m.V)


def logits_at(cfg: Dict[str, Any], w: Dict[str, Any], tokens: jax.Array,
              start: int, n_pos: int, quant: Optional[str] = None) -> jax.Array:
    """float32 logits ``(n_pos, vocab)`` at positions ``start .. start+n_pos-1``
    of one sequence ``tokens`` (causal: padding after the real tokens does not
    change them).  ``start + n_pos`` must not pass ``len(tokens)``."""
    m = dims(cfg)
    chunks = next(n for n in (8, 4, 2, 1) if m.V % n == 0)
    return _logits_at(m, quant, n_pos, chunks, w, jnp.asarray(tokens, jnp.int32),
                      jnp.int32(start))
