"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program (``repro``).  It
maps the published config keys onto the program's ``ArchConfig``, the file's
``serve`` section onto ``ServeConfig``, and the benchmark's weights onto the
program's parameter tree (checked leaf by leaf against what the program's own
``init`` would build).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from sbench.weights import Dims


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


def serve_config(cfg: Dict[str, Any]):
    from repro.api import ServeConfig

    return ServeConfig(reduced=False, **cfg["serve"])


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


def program_params(cfg: Dict[str, Any], arch, w: Dict[str, Any]):
    """The program's parameter tree holding the benchmark's weights ``w``
    (consumed).  Raises if a leaf differs from what ``init`` builds."""
    from repro.distributed.sharding import unzip_params
    from repro.models import build_model

    params = _to_program(Dims.of(cfg), arch.padded_heads, arch.padded_vocab, w)
    want, _ = unzip_params(jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0)))
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got_s != want_s:
        raise ValueError(f"weights do not fit the program's tree:\n{got_s}\n!=\n{want_s}")
    return params


def build(cfg: Dict[str, Any], w: Dict[str, Any]):
    """A ``StreamServe`` serving the weights ``w``."""
    from repro.api import StreamServe

    arch = arch_config(cfg)
    return StreamServe(serve_config(cfg), params=program_params(cfg, arch, w), arch_cfg=arch)
