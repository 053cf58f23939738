"""The Qwen2 and Qwen3 dense decoders (``model_type`` ``qwen2``, ``qwen3``).

Weights are in the published layout (Hugging Face names, matrices stored as
``(in, out)``, layers stacked on a leading axis), shared by the program
adapter below and the plain reference ``reference/qwen.py``.  Qwen3
normalises each query and key head; Qwen2 adds a bias to the q, k and v
projections.

Costs count real heads only: the program pads the query heads of each KV
group (40 -> 48 for qwen2.5-14b), and the padding is not work the model needs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from sbench.flops import BF16, attn_pairs
from sbench.weights import random_tree


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int            # hidden_size
    n_layers: int     # num_hidden_layers
    H: int            # num_attention_heads
    K: int            # num_key_value_heads
    D: int            # head_dim
    f: int            # intermediate_size
    V: int            # vocab_size
    tied: bool        # tie_word_embeddings
    qk_norm: bool     # qwen3: per-head RMSNorm on q and k
    qkv_bias: bool    # qwen2: bias on the q, k and v projections
    eps: float        # rms_norm_eps
    theta: float      # rope_theta

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Dims":
        model_type = cfg["model_type"]
        if model_type not in ("qwen2", "qwen3"):
            raise ValueError(f"the qwen family has no model_type {model_type!r}")
        d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(
            d=d, n_layers=int(cfg["num_hidden_layers"]), H=H,
            K=int(cfg["num_key_value_heads"]),
            D=int(cfg.get("head_dim") or d // H),
            f=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
            tied=bool(cfg["tie_word_embeddings"]),
            qk_norm=model_type == "qwen3",
            qkv_bias=model_type == "qwen2" or bool(cfg.get("attention_bias", False)),
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        )


# ------------------------------------------------------------------ weights
def shapes(m: Dims) -> Dict[str, Any]:
    """name -> (shape, init) where init is ("normal", std) or ("one", std)."""
    L, d, f = m.n_layers, m.d, m.f
    q, kv = m.H * m.D, m.K * m.D
    layers = {
        "ln1": ((L, d), ("one", 0.1)),
        "ln2": ((L, d), ("one", 0.1)),
        "wq": ((L, d, q), ("normal", d ** -0.5)),
        "wk": ((L, d, kv), ("normal", d ** -0.5)),
        "wv": ((L, d, kv), ("normal", d ** -0.5)),
        "wo": ((L, q, d), ("normal", q ** -0.5)),
        "gate": ((L, d, f), ("normal", d ** -0.5)),
        "up": ((L, d, f), ("normal", d ** -0.5)),
        "down": ((L, f, d), ("normal", f ** -0.5)),
    }
    if m.qkv_bias:
        layers.update(bq=((L, q), ("normal", 0.1)), bk=((L, kv), ("normal", 0.1)),
                      bv=((L, kv), ("normal", 0.1)))
    if m.qk_norm:
        layers.update(q_norm=((L, m.D), ("one", 0.1)), k_norm=((L, m.D), ("one", 0.1)))
    top = {"embed": ((m.V, d), ("normal", 0.02)), "norm": ((d,), ("one", 0.1))}
    if not m.tied:
        top["head"] = ((d, m.V), ("normal", 0.02))
    return {**top, "layers": layers}


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """bf16 weights for ``cfg`` (config JSON) from ``seed``, on the device."""
    return random_tree(shapes(Dims.of(cfg)), seed)


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Bytes of bf16 keys and values one cached token holds over all layers."""
    m = Dims.of(cfg)
    return 2 * m.n_layers * m.K * m.D * 2


# ------------------------------------------------------------------ the program
def arch_config(cfg: Dict[str, Any]):
    from repro.configs.base import ArchConfig

    m = Dims.of(cfg)
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=m.n_layers, d_model=m.d,
        n_heads=m.H, n_kv_heads=m.K, d_ff=m.f, vocab_size=m.V, head_dim=m.D,
        qk_norm=m.qk_norm, qkv_bias=m.qkv_bias, rope_theta=m.theta,
        dtype=cfg["torch_dtype"], norm_eps=m.eps, tie_embeddings=m.tied,
        scan_block=1, source=cfg["source"],
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _to_program(m: Dims, Hp: int, Vp: int, w: Dict[str, Any]) -> Dict[str, Any]:
    L, d, H, K, D = m.n_layers, m.d, m.H, m.K, m.D
    G, Gp = H // K, Hp // K
    lw = w["layers"]

    def pad_heads(x, axis):
        """(..., H, ...) -> (..., Hp, ...) in the program's group-major padded
        layout: head j sits at slot (j // G) * Gp + j % G, padding is zero."""
        if Hp == H:
            return x
        shape = list(x.shape)
        x = x.reshape(shape[:axis] + [K, G] + shape[axis + 1:])
        pad = [(0, 0)] * x.ndim
        pad[axis + 1] = (0, Gp - G)
        return jnp.pad(x, pad).reshape(shape[:axis] + [Hp] + shape[axis + 1:])

    attn = {
        "wq": pad_heads(lw["wq"].reshape(L, d, H, D), 2),
        "wk": lw["wk"].reshape(L, d, K, D),
        "wv": lw["wv"].reshape(L, d, K, D),
        "wo": pad_heads(lw["wo"].reshape(L, H, D, d), 1),
    }
    if m.qkv_bias:
        attn["bq"] = pad_heads(lw["bq"].reshape(L, H, D), 1)
        attn["bk"] = lw["bk"].reshape(L, K, D)
        attn["bv"] = lw["bv"].reshape(L, K, D)
    if m.qk_norm:
        attn["q_norm"], attn["k_norm"] = lw["q_norm"], lw["k_norm"]
    emb = {"table": jnp.pad(w["embed"], ((0, Vp - m.V), (0, 0)))}
    if not m.tied:
        emb["head"] = jnp.pad(w["head"], ((0, 0), (0, Vp - m.V)))
    block = {"norm1": lw["ln1"], "attn": attn, "norm2": lw["ln2"],
             "mlp": {"wi": lw["up"], "wg": lw["gate"], "wo": lw["down"]}}
    return {"embedding": emb, "blocks": {"0": block}, "final_norm": w["norm"]}


def to_program(cfg: Dict[str, Any], arch, w: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (one uniform block stack) holding ``w``
    (consumed)."""
    return _to_program(Dims.of(cfg), arch.padded_heads, arch.padded_vocab, w)


# ------------------------------------------------------------------ costs
def decode_attention_cost(m: Dims, rows: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """FLOPs and bytes of one decode-attention call (one layer) over live
    ``rows`` of ``(cached_len, fed)``: QK^T and PV, reading each row's live
    keys and values once, reading q and writing o."""
    flops = nbytes = 0.0
    for cached, fed in rows:
        flops += 4.0 * m.H * m.D * attn_pairs(cached, fed)
        nbytes += BF16 * m.D * (2 * m.K * (cached + fed) + 2 * m.H * fed)
    return flops, nbytes


def flash_attention_cost(m: Dims, prompt_lens: Iterable[int]) -> Tuple[float, float]:
    """FLOPs and bytes of one causal prefill-attention call (one layer) over
    the live prompt lengths of its rows."""
    flops = nbytes = 0.0
    for n in prompt_lens:
        flops += 4.0 * m.H * m.D * attn_pairs(0, n)
        nbytes += BF16 * m.D * n * (2 * m.H + 2 * m.K)
    return flops, nbytes


def linear_params(m: Dims) -> int:
    """Matrix parameters of one layer (q, k, v, o and the SwiGLU MLP)."""
    return m.d * m.D * (2 * m.H + 2 * m.K) + 3 * m.d * m.f


def decode_step_flops(cfg: Dict[str, Any], rows: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs one decode/verify call needs: every fed token goes through
    every layer and the output head; attention over each row's live cache."""
    m = Dims.of(cfg)
    rows = list(rows)
    fed = sum(f for _, f in rows)
    attn, _ = decode_attention_cost(m, rows)
    return m.n_layers * (2.0 * linear_params(m) * fed + attn) + 2.0 * m.d * m.V * fed


def prefill_flops(cfg: Dict[str, Any], prompt_lens: Iterable[int]) -> float:
    """Model FLOPs one prefill call needs: every prompt token through every
    layer, causal attention, and the output head at each row's last token."""
    m = Dims.of(cfg)
    lens = list(prompt_lens)
    attn, _ = flash_attention_cost(m, lens)
    return (m.n_layers * (2.0 * linear_params(m) * sum(lens) + attn)
            + 2.0 * m.d * m.V * len(lens))


def _per_layer(cost):
    def calls(cfg: Dict[str, Any], work) -> Tuple[int, float, float]:
        m = Dims.of(cfg)
        return (m.n_layers, *cost(m, work))
    return calls


# kernel name (the Pallas kernel's ``name=``) -> its calls in one program
# execution: one call per layer
KERNELS = {"decode_attention": _per_layer(decode_attention_cost),
           "flash_attention": _per_layer(flash_attention_cost)}
