"""Random weights from the seed, made on the device in one jitted call.

A family module (``families/<family>.py``) describes its weights as a nested
dict of leaves ``name -> (shape, (kind, std))`` and makes them with
``random_tree``: each leaf is ``N(0, std)`` in bfloat16, plus 1 where
``kind`` is ``"one"``, from its own fold of ``weight_key(seed)``.  Groups of
layers are sub-dicts and need not be alike.  Making the same seed twice
gives the same bits: the reference regenerates its weights after the
program's state has been freed, instead of taking any array the program has
held.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sbench.traffic import seed_words


def weight_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed_words(seed, 3).integers(0, 2**31 - 1)))


def _flat(spec: Dict[str, Any], prefix: Tuple[str, ...] = ()) -> Tuple[Any, ...]:
    """Leaves depth first, in the dict's order: ``(path, shape, kind, std)``."""
    out = []
    for name, v in spec.items():
        if isinstance(v, dict):
            out.extend(_flat(v, prefix + (name,)))
        else:
            (shape, (kind, std)) = v
            out.append((prefix + (name,), tuple(shape), kind, float(std)))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0,))
def _make(leaves: Tuple[Any, ...], key: jax.Array) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for i, (path, shape, kind, std) in enumerate(leaves):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16) * std
        if kind == "one":
            x = x + 1
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x.astype(jnp.bfloat16)
    return out


def random_tree(spec: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """bf16 leaves of the nested ``spec`` from ``seed``, on the device."""
    return _make(_flat(spec), weight_key(seed))
