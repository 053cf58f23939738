"""The float32 reference against the program's own forward pass, at a tiny
size on the CPU, for both block types (qwen3: q/k norm, tied head; qwen2:
QKV bias, untied head, query heads the program pads)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from streambench_testlib import FIX, spec
from sbench import program


@pytest.mark.parametrize("name", ["qwen3-tiny", "qwen2-tiny"])
def test_reference_matches_program_logits(name):
    from repro.models import build_model

    cfg = dict(spec.load_json(FIX / f"{name}.json"), torch_dtype="float32")
    fam, qwen = spec.family(cfg), spec.reference(cfg)
    w = jax.tree.map(lambda x: x.astype(jnp.float32), fam.make_weights(cfg, 2**31 + 5))
    arch = fam.arch_config(cfg)
    params = program.program_params(fam, cfg, arch, jax.tree.map(jnp.copy, w))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40).astype(np.int32)
    got = build_model(arch).forward(params, {"tokens": jnp.asarray(toks)[None]})[0]
    want = qwen.logits_at(cfg, w, np.pad(toks, (0, 24)), 0, 40)
    got = np.asarray(got[:, : cfg["vocab_size"]])
    assert np.abs(np.asarray(want) - got).max() < 1e-4 * np.abs(got).max() + 1e-5
    # causal: padding after the real tokens leaves earlier positions alone
    again = qwen.logits_at(cfg, w, np.pad(toks[:30], (0, 34)), 10, 20)
    np.testing.assert_allclose(np.asarray(again), np.asarray(want)[10:30], rtol=1e-5, atol=1e-5)


def test_int8_control_differs_from_reference():
    cfg = spec.load_json(FIX / "qwen3-tiny.json")
    qwen = spec.reference(cfg)
    w = spec.family(cfg).make_weights(cfg, 3)
    toks = np.arange(64, dtype=np.int32) % cfg["vocab_size"]
    ref = np.asarray(qwen.logits_at(cfg, w, toks, 0, 32))
    low = np.asarray(qwen.logits_at(cfg, w, toks, 0, 32, quant="int8"))
    err = np.abs(ref - low).max()
    assert 1e-4 < err < 0.5 * np.abs(ref).max()
