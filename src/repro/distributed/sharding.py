"""Logical-axis sharding (MaxText-style) for the StreamServe reproduction.

Parameters are created as :class:`P` leaves — ``(value, axes)`` — where
``axes`` is a tuple of *logical* axis names (or ``None``).  A rules table maps
logical names to mesh axes; :func:`logical_to_spec` resolves a logical tuple
into a concrete :class:`jax.sharding.PartitionSpec`, greedily skipping mesh
axes that are already consumed by an earlier dimension of the same tensor and
dropping mappings whose dimension is smaller than the shard count (those are
replicated — e.g. 2 KV heads on a 16-way model axis).

Mesh axes
---------
``pod``    cross-pod data parallelism (multi-pod mesh only)
``data``   within-pod data parallelism / FSDP / context-parallel KV
``model``  tensor parallelism (heads / mlp / experts / vocab) and
           sequence-sharded decode KV
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec

AxisName = Optional[str]
LogicalAxes = Tuple[AxisName, ...]


class P:
    """A parameter leaf: value (or ShapeDtypeStruct) + logical axes.

    Registered as a pytree node with ``axes`` as static aux data, so vmap/jit
    transparently transform ``value`` while the logical axes ride along.
    """

    __slots__ = ("value", "axes")

    def __init__(self, value: Any, axes: LogicalAxes):
        self.value = value
        self.axes = tuple(axes)

    def __repr__(self) -> str:
        return f"P({self.value!r}, axes={self.axes})"


jax.tree_util.register_pytree_node(
    P,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: P(children[0], axes),
)


Rules = Tuple[Tuple[str, Tuple[str, ...]], ...]

# Order matters: earlier entries win contested mesh axes.
DEFAULT_RULES: Rules = (
    ("batch", ("pod", "data")),
    ("ctx", ("data",)),        # context/sequence parallel activations
    ("kv_seq", ("model",)),    # decode KV cache sequence dim (flash-decode)
    ("experts", ("model",)),
    ("heads", ("model",)),
    ("kv", ("model",)),
    ("mlp", ("model",)),
    ("vocab", ("model",)),
    ("embed", ("data",)),      # FSDP weight sharding
    ("conv", ("model",)),      # mamba conv channels
    ("inner", ("model",)),     # mamba d_inner
)

# FSDP across pods as well — used by very large models (jamba-398b) so weights
# and optimizer state scale with the full device count.
POD_FSDP_RULES: Rules = tuple(
    (name, ("pod", "data") if name == "embed" else axes) for name, axes in DEFAULT_RULES
)

# Inference rules: NO FSDP on the embed dim.  FSDP weight sharding forces an
# all-gather of every weight on every decode step (3.5 GB/step/device at
# qwen2.5-14b decode_32k — dry-run measured); model-axis tensor parallelism
# alone already fits serving weights (28 GB / 16-way = 1.75 GB/device) with
# zero per-step weight collectives.  Selected via ``use_rules`` by the
# serve-path lowering (see EXPERIMENTS.md §Perf, decode iteration B).
INFERENCE_RULES: Rules = tuple(
    (name, () if name == "embed" else axes) for name, axes in DEFAULT_RULES
)

# ZeRO-1 for SMALL-model training: weights replicated over data (their bf16
# copy fits per device), optimizer state still FSDP-sharded on embed.  Full
# FSDP (ZeRO-3) re-gathers every weight per layer per pass — 339 GB/device
# of all-gather at qwen3-1.7b train_4k (dry-run measured) for a model whose
# whole weight set is 4 GB; ZeRO-1 pays ONE weight update gather per step.
# Applied by the train lowering when 2*n_params fits the per-device budget.
ZERO1_PARAM_RULES: Rules = INFERENCE_RULES
ZERO1_WEIGHT_BYTES_LIMIT = 8e9  # replicated bf16 weights budget per device

_ACTIVE_RULES: Rules = DEFAULT_RULES


class use_rules:
    """Context manager swapping the rules used by ``constraint`` (the
    activation sharding constraints inside model code)."""

    def __init__(self, rules: Rules):
        self.rules = rules
        self._prev: Optional[Rules] = None

    def __enter__(self):
        global _ACTIVE_RULES
        self._prev = _ACTIVE_RULES
        _ACTIVE_RULES = self.rules
        return self.rules

    def __exit__(self, *exc):
        global _ACTIVE_RULES
        _ACTIVE_RULES = self._prev
        return False


def active_rules() -> Rules:
    return _ACTIVE_RULES


def _rules_lookup(rules: Rules, name: str) -> Tuple[str, ...]:
    for key, axes in rules:
        if key == name:
            return axes
    return ()


def logical_to_spec(
    axes: LogicalAxes,
    mesh: Mesh,
    rules: Rules = DEFAULT_RULES,
    shape: Optional[Sequence[int]] = None,
) -> PartitionSpec:
    """Resolve logical axes into a PartitionSpec for ``mesh``.

    * mesh axes absent from ``mesh`` are dropped (single-pod meshes have no
      ``pod`` axis);
    * a mesh axis already used by an earlier dim of this tensor is skipped;
    * if ``shape`` is given and the dim size is smaller than the shard count
      the mapping is dropped (replicate) — GSPMD would pad > 2x otherwise.
    """
    used: set = set()
    out = []
    for i, name in enumerate(axes):
        if name is None:
            out.append(None)
            continue
        mesh_axes = [
            ax
            for ax in _rules_lookup(rules, name)
            if ax in mesh.axis_names and ax not in used
        ]
        if not mesh_axes:
            out.append(None)
            continue
        n_shards = 1
        for ax in mesh_axes:
            n_shards *= mesh.shape[ax]
        if shape is not None and (shape[i] < n_shards or shape[i] % n_shards != 0):
            # replicate rather than let GSPMD pad (jit in_shardings would
            # reject indivisible dims outright)
            out.append(None)
            continue
        used.update(mesh_axes)
        out.append(tuple(mesh_axes) if len(mesh_axes) > 1 else mesh_axes[0])
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(
    axes: LogicalAxes,
    mesh: Mesh,
    rules: Rules = DEFAULT_RULES,
    shape: Optional[Sequence[int]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, mesh, rules, shape))


def _is_p(x: Any) -> bool:
    return isinstance(x, P)


def unzip_params(tree: Any) -> Tuple[Any, Any]:
    """Split a tree with :class:`P` leaves into (values, logical-axes) trees."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_p)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_p)
    return values, axes


def tree_specs(axes_tree: Any, values_tree: Any, mesh: Mesh, rules: Rules = DEFAULT_RULES) -> Any:
    """PartitionSpec tree matching ``values_tree`` (uses shapes for divisibility)."""

    def _one(axes: LogicalAxes, val: Any) -> PartitionSpec:
        shape = getattr(val, "shape", None)
        return logical_to_spec(axes, mesh, rules, shape)

    return jax.tree.map(_one, axes_tree, values_tree, is_leaf=lambda x: isinstance(x, tuple))


def tree_shardings(axes_tree: Any, values_tree: Any, mesh: Mesh, rules: Rules = DEFAULT_RULES) -> Any:
    specs = tree_specs(axes_tree, values_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, PartitionSpec))


def shard_params(params: Any, axes_tree: Any, mesh: Mesh, rules: Rules = DEFAULT_RULES) -> Any:
    """device_put a realised param tree onto ``mesh`` per the rules."""
    shardings = tree_shardings(axes_tree, params, mesh, rules)
    return jax.device_put(params, shardings)


def stack_axes(axes: LogicalAxes) -> LogicalAxes:
    """Logical axes for a layer-stacked (scanned) parameter."""
    return ("layer",) + tuple(axes)


def constraint(x: jax.Array, axes: LogicalAxes, mesh: Optional[Mesh | AbstractMesh] = None, rules: Optional[Rules] = None) -> jax.Array:
    """with_sharding_constraint via logical axes (no-op without a mesh).
    Uses the ambient rules (``use_rules``) unless overridden."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_spec(axes, mesh, rules or _ACTIVE_RULES, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh() -> Optional[AbstractMesh]:
    """The mesh made active by ``jax.set_mesh`` (readable inside jit), or
    None outside any mesh — every mesh-dependent path is then a no-op."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh
