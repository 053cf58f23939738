"""Random weights from the seed, made on the device in one jitted call.

The layout is the published one (Hugging Face names, matrices stored as
``(in, out)``, layers stacked on a leading axis) and is shared by the program
adapter (``program.py``) and the plain reference (``reference/``).  Making
the same seed twice gives the same bits: the reference regenerates its
weights after the program's state has been freed, instead of taking any
array the program has held.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from sbench.traffic import seed_words


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
        family = cfg["model_type"]
        if family not in ("qwen2", "qwen3"):
            raise ValueError(f"no weights layout for model_type {family!r}")
        d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(
            d=d, n_layers=int(cfg["num_hidden_layers"]), H=H,
            K=int(cfg["num_key_value_heads"]),
            D=int(cfg.get("head_dim") or d // H),
            f=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
            tied=bool(cfg["tie_word_embeddings"]),
            qk_norm=family == "qwen3",
            qkv_bias=family == "qwen2" or bool(cfg.get("attention_bias", False)),
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        )


def weight_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed_words(seed, 3).integers(0, 2**31 - 1)))


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


@functools.partial(jax.jit, static_argnums=(0,))
def _make(m: Dims, key: jax.Array) -> Dict[str, Any]:
    spec = shapes(m)
    flat = [(k, v) for k, v in spec.items() if k != "layers"]
    flat += [(f"layers/{k}", v) for k, v in spec["layers"].items()]
    out: Dict[str, Any] = {"layers": {}}
    for i, (name, (shape, (kind, std))) in enumerate(flat):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16) * std
        if kind == "one":
            x = x + 1
        x = x.astype(jnp.bfloat16)
        if name.startswith("layers/"):
            out["layers"][name[len("layers/"):]] = x
        else:
            out[name] = x
    return out


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """bf16 weights for ``cfg`` (config JSON) from ``seed``, on the device."""
    return _make(Dims.of(cfg), weight_key(seed))
