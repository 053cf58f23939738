"""Dry-run machinery: lower + compile every (arch × shape × mesh) cell and
extract memory / FLOP / collective statistics for the roofline analysis.

Import this ONLY from an entrypoint that has already set
``XLA_FLAGS=--xla_force_host_platform_device_count=...`` (launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import get_config
from repro.configs.base import ArchConfig, SHAPES, ShapeConfig, shape_applicable
from repro.distributed.sharding import (
    DEFAULT_RULES,
    INFERENCE_RULES,
    tree_specs,
    unzip_params,
    use_rules,
)
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.models import build_model
from repro.serving.cost_model import TPU_V5E
from repro.training.optimizer import OptConfig
from repro.training.train_loop import make_train_step, opt_state_axes

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "pred": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"=\s*([^=]+?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\("
)


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind (SPMD module shapes)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _INSTR_RE.finditer(hlo_text):
        lhs, op, start = m.group(1), m.group(2), m.group(3)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        if start:  # async start ops carry (operand, result) tuples
            nbytes //= 2
        out[op] += nbytes
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """Training / prefill batch structure for the given shape."""
    B = shape.global_batch
    S = shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if cfg.is_encdec:
        # seq_len = source frames; target length seq_len // 4 (DESIGN.md §5)
        tgt = max(S // 4, 16) if shape.kind == "train" else 1
        return {
            "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), dt),
            "tokens": jax.ShapeDtypeStruct((B, tgt), jnp.int32),
        }
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        n_text = S - cfg.frontend.n_tokens
        return {
            "patches": jax.ShapeDtypeStruct((B, cfg.frontend.n_tokens, cfg.d_model), dt),
            "tokens": jax.ShapeDtypeStruct((B, n_text), jnp.int32),
        }
    return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}


def batch_axes(batch: Dict[str, Any]) -> Dict[str, tuple]:
    return {
        k: ("batch",) + (None,) * (v.ndim - 1) for k, v in batch.items()
    }


_CACHE_AXES_BY_KEY = {
    "k": ("batch", "kv_seq", "kv", None),
    "v": ("batch", "kv_seq", "kv", None),
    "kv_pos": ("batch", "kv_seq"),
    "conv": ("batch", None, "conv"),
    "state": ("batch", "heads", None, None),
    "cross_k": ("batch", None, "kv", None),
    "cross_v": ("batch", None, "kv", None),
    "len": ("batch",),
    "mem_len": ("batch",),
}


def cache_axes(cache_sds: Any) -> Any:
    def one(path, leaf):
        key = None
        for p in reversed(path):
            if hasattr(p, "key"):
                key = p.key
                break
        axes = _CACHE_AXES_BY_KEY[key]
        under_blocks = any(getattr(p, "key", None) == "blocks" for p in path)
        return (("layer",) + axes) if under_blocks else axes

    return jax.tree_util.tree_map_with_path(one, cache_sds)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str
    seconds: float
    flops_per_device: float = 0.0
    bytes_per_device: float = 0.0
    xla_flops_per_device: float = 0.0
    xla_bytes_per_device: float = 0.0
    peak_memory_per_device: int = 0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    error: str = ""

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _shardings(axes_tree, sds_tree, mesh, rules=DEFAULT_RULES):
    specs = tree_specs(axes_tree, sds_tree, mesh, rules)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def lower_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str = "single",
    spec_tokens: int = 0,
) -> CellResult:
    """Lower + compile one cell; returns stats.  ``spec_tokens > 0`` lowers the
    speculative verify step (T = spec_tokens + 1) instead of plain decode."""
    t0 = time.perf_counter()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return CellResult(arch, shape_name, mesh_kind, "skipped", 0.0, error=why)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh_chips(mesh)
    model = build_model(cfg)

    # serving steps use the inference rules (no per-step FSDP weight
    # all-gathers — see sharding.INFERENCE_RULES).  Training: full FSDP
    # (ZeRO-3) for big models; ZeRO-1 (replicated weights, sharded optimizer
    # state) when the bf16 weights fit per device — per-layer weight gathers
    # dominate the collective term for small models otherwise.
    from repro.distributed.sharding import ZERO1_PARAM_RULES, ZERO1_WEIGHT_BYTES_LIMIT

    if shape.kind == "train":
        zero1 = 2.0 * cfg.n_params() / max(mesh.shape["model"], 1) <= ZERO1_WEIGHT_BYTES_LIMIT
        rules = ZERO1_PARAM_RULES if zero1 else DEFAULT_RULES
        opt_rules = DEFAULT_RULES  # optimizer state always FSDP-sharded
    else:
        rules = opt_rules = INFERENCE_RULES

    params_p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sds, params_axes = unzip_params(params_p)
    params_sh = _shardings(params_axes, params_sds, mesh, rules)

    with jax.set_mesh(mesh), use_rules(rules):
        if shape.kind == "train":
            init_opt, train_step = make_train_step(model, OptConfig())
            opt_sds = jax.eval_shape(init_opt, params_sds)
            opt_axes = opt_state_axes(cfg.optimizer, params_axes, params_sds)
            opt_sh = _shardings(opt_axes, opt_sds, mesh, opt_rules)
            batch = batch_specs(cfg, shape)
            batch_sh = _shardings(batch_axes(batch), batch, mesh)

            fn = jax.jit(train_step, in_shardings=(params_sh, opt_sh, batch_sh),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_sds, opt_sds, batch)
        elif shape.kind == "prefill":
            batch = batch_specs(cfg, shape)
            batch_sh = _shardings(batch_axes(batch), batch, mesh)

            def prefill_step(params, b):
                return model.prefill(params, b, max_len=shape.seq_len)

            fn = jax.jit(prefill_step, in_shardings=(params_sh, batch_sh))
            lowered = fn.lower(params_sds, batch)
        else:  # decode
            B = shape.global_batch
            T = spec_tokens + 1
            cross_len = cfg.frontend.n_tokens if cfg.is_encdec else None
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(B, shape.seq_len, cross_len)
            )
            c_axes = cache_axes(cache_sds)
            cache_sh = _shardings(c_axes, cache_sds, mesh)
            tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
            tok_sh = _shardings({"t": ("batch", None)}, {"t": tokens}, mesh)["t"]

            fn = jax.jit(model.decode_step, in_shardings=(params_sh, cache_sh, tok_sh),
                         donate_argnums=(1,))
            lowered = fn.lower(params_sds, cache_sds, tokens)

        compiled = lowered.compile()

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    # Trip-count-correct analysis: XLA's cost_analysis counts while bodies
    # ONCE, which undercounts scan-over-layers models by ~n_layers; the HLO
    # analyzer multiplies loop bodies by their known trip counts.
    from repro.launch.hlo_analysis import analyze

    hlo_text = compiled.as_text()
    hcost = analyze(hlo_text)
    coll = {k: int(v) for k, v in hcost.collectives.items()}
    res = CellResult(
        arch=arch,
        shape=shape_name,
        mesh=mesh_kind,
        status="ok",
        seconds=round(time.perf_counter() - t0, 1),
        flops_per_device=float(hcost.flops),
        bytes_per_device=float(hcost.bytes),
        xla_flops_per_device=float(cost.get("flops", 0.0)),
        xla_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        peak_memory_per_device=int(getattr(mem, "temp_size_in_bytes", 0))
        + int(getattr(mem, "output_size_in_bytes", 0)),
        argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
        collectives=coll,
    )
    return res


def roofline_terms(res: CellResult, chips: int) -> Dict[str, float]:
    """Three-term roofline (seconds) from per-device dry-run stats, priced
    on the v5e chips the production mesh is made of."""
    return {
        "compute_s": res.flops_per_device / TPU_V5E.peak_flops,
        "memory_s": res.bytes_per_device / TPU_V5E.hbm_bw,
        "collective_s": res.collectives.get("total", 0) / TPU_V5E.interconnect_bw,
    }
