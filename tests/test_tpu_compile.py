"""The serving path's Pallas kernels compile for a TPU v5e at qwen3-1.7b
widths.

Nothing runs: each test lowers a kernel against a *described* v5e chip and
has the TPU compiler (libtpu, installed on the CPU host) build it, which is
where Mosaic refuses block shapes that interpret mode accepts.  The topology
is described inside a module fixture — never at import — so only the worker
that runs this file loads libtpu, and every worker collects the same tests.
All compile-only tests stay in this one file for that reason.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import engine
from repro.distributed.sharding import unzip_params
from repro.kernels import ops
from repro.kernels.decode_attention import (
    decode_attention_paged_pallas,
    decode_attention_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import build_model

CFG = get_config("qwen3-1.7b")
H, K, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
B, S = 16, 2048            # ServeConfig.paper_stream_pairs: max_batch, max_len
PAGE, N_PAGES = 16, 2048   # kv_block_size, pages per pair
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation cache
    off: entries written for a described chip cannot be read back without
    one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # no libtpu, or it is held elsewhere
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("T", [1, 9])
def test_decode_attention_compiles(one_chip, T):
    _assert_kernel(decode_attention_pallas.lower(
        _sds(one_chip, (B, T, H, D)),
        _sds(one_chip, (B, S, K, D)),
        _sds(one_chip, (B, S, K, D)),
        _sds(one_chip, (B,), jnp.int32),
        kv_positions=_sds(one_chip, (B, S), jnp.int32),
    ))


@pytest.mark.parametrize("T", [1, 9])
def test_decode_attention_paged_compiles(one_chip, T):
    _assert_kernel(decode_attention_paged_pallas.lower(
        _sds(one_chip, (B, T, H, D)),
        _sds(one_chip, (N_PAGES, K, PAGE, D)),
        _sds(one_chip, (N_PAGES, K, PAGE, D)),
        _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B, S // PAGE), jnp.int32),
    ))


def test_flash_attention_compiles(one_chip):
    Bp, Sq = 4, 512  # admit_batch rows of one prefill bucket
    _assert_kernel(flash_attention_pallas.lower(
        _sds(one_chip, (Bp, Sq, H, D)),
        _sds(one_chip, (Bp, Sq, K, D)),
        _sds(one_chip, (Bp, Sq, K, D)),
    ))


def _lane_decode_lowered(one_chip, monkeypatch, paged, T):
    """``engine._lane_decode`` of a 2048-token, 16-slot lane at full width,
    lowered for the described chip; returns (lowered, params, cache) with
    params and cache as placed shapes."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)  # host backend is CPU
    model = build_model(CFG)

    def placed(fn, *a):
        return jax.tree.map(
            lambda x: _sds(one_chip, x.shape, x.dtype), jax.eval_shape(fn, *a)
        )

    params = placed(lambda k: unzip_params(model.init(k))[0], jax.random.PRNGKey(0))
    if paged:
        cache = placed(lambda: model.init_paged_cache(B, N_PAGES, PAGE, S))
    else:
        cache = placed(lambda: model.init_cache(B, S))
    lowered = engine._lane_decode.lower(
        model.decode_step, params, cache, _sds(one_chip, (B, T), jnp.int32)
    )
    return lowered, params, cache


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_verify_step_compiles(one_chip, monkeypatch, paged):
    """One whole verify step (T = 9) of a 2048-token, 16-slot lane at full
    width: the decode kernel sits inside the compiled program."""
    _assert_kernel(_lane_decode_lowered(one_chip, monkeypatch, paged, 9)[0])


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$")


def _whole_cache_moves(hlo: str, stacked):
    """Instructions of an optimized HLO module that copy a whole stacked
    cache leaf, or write a whole layer of one back with a
    dynamic-update-slice."""
    shapes, moves = {}, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, dims, op, operands = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        shapes[name] = shape
        if shape not in stacked:
            continue
        if op in ("copy", "copy-start"):
            moves.append(line.strip())
        elif op == "dynamic-update-slice":
            update = re.findall(r"%([^\s,)]+)", operands)[1]
            if math.prod(shapes[update]) == math.prod(shape[1:]):
                moves.append(line.strip())
    return moves


@pytest.mark.parametrize("T", [1, 9])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_decode_keeps_kv_in_place(one_chip, monkeypatch, paged, T):
    """The decode step writes its new KV rows into the donated stacked cache:
    the compiled program holds no copy of a whole stacked K/V leaf (the
    scan's re-stacked outputs copied into the donated buffer) and no
    dynamic-update-slice of a whole layer (the scan's per-layer write-back),
    and every cache leaf is aliased input to output."""
    lowered, params, cache = _lane_decode_lowered(one_chip, monkeypatch, paged, T)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    stacked = {a.shape for a in jax.tree.leaves(cache["blocks"])}
    assert stacked == ({(CFG.n_layers, N_PAGES, K, PAGE, D)} if paged else
                       {(CFG.n_layers, B, S, K, D), (CFG.n_layers, B, S)})
    assert _whole_cache_moves(hlo, stacked) == []
    # flat argument order: params, cache, tokens
    n_params = len(jax.tree.leaves(params))
    cache_args = set(range(n_params, n_params + len(jax.tree.leaves(cache))))
    aliased = {int(a) for a in re.findall(
        r"\{[\d,]*\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo
    )}
    assert cache_args <= aliased, sorted(cache_args - aliased)
