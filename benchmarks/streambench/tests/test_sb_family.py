"""Model families are files found by name: a new family (weights with layer
groups unlike the qwen stack, its reference, its costs) is taken up from new
files and entries alone; and the qwen family reads what it read before it
became a file of its own (numbers taken from the code before the move)."""
import hashlib
import json
import shutil
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from streambench_testlib import BENCH, FIX, spec
from sbench import harness

TOY_FAMILY = '''
"""Toy family: an embedding, one "first" layer and a stack of "rest" layers
of another width and with a leaf the first has not; no attention."""
from sbench.weights import random_tree


def shapes(cfg):
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["rest_layers"]
    return {"embed": ((V, d), ("normal", 0.5)),
            "first": {"w1": ((d, cfg["first_width"]), ("normal", d ** -0.5)),
                      "w2": ((cfg["first_width"], d), ("normal", 0.1))},
            "rest": {"ln": ((L, d), ("one", 0.1)),
                     "w1": ((L, d, cfg["rest_width"]), ("normal", d ** -0.5)),
                     "w2": ((L, cfg["rest_width"], d), ("normal", 0.1))}}


def make_weights(cfg, seed):
    return random_tree(shapes(cfg), seed)


def arch_config(cfg):
    raise NotImplementedError("the program has no toy model")


def to_program(cfg, arch, w):
    raise NotImplementedError("the program has no toy model")


def kv_bytes_per_token(cfg):
    return 2 * cfg["hidden_size"] * (1 + cfg["rest_layers"])


def _layer_flops(cfg, tokens):
    d = cfg["hidden_size"]
    return 4.0 * d * tokens * (cfg["first_width"] + cfg["rest_layers"] * cfg["rest_width"])


def decode_step_flops(cfg, rows):
    return _layer_flops(cfg, sum(f for _, f in rows))


def prefill_flops(cfg, prompt_lens):
    return _layer_flops(cfg, sum(prompt_lens))


def _rest_mlp_cost(cfg, rows):
    """The toy kernel runs in the "rest" layers only."""
    d, fed = cfg["hidden_size"], sum(f for _, f in rows)
    return cfg["rest_layers"], 4.0 * d * cfg["rest_width"] * fed, 2.0 * fed * d


KERNELS = {"toy_mlp": _rest_mlp_cost}
'''

TOY_REFERENCE = '''
"""Plain float32 toy model: each layer adds an MLP of the causal running
mean of the tokens so far."""
import jax
import jax.numpy as jnp


def _mlp(x, w1, w2):
    mean = jnp.cumsum(x, 0) / jnp.arange(1, x.shape[0] + 1)[:, None]
    return x + jax.nn.silu(mean @ w1.astype(jnp.float32)) @ w2.astype(jnp.float32)


def logits_at(cfg, w, tokens, start, n_pos, quant=None):
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    x = _mlp(x, w["first"]["w1"], w["first"]["w2"])
    for i in range(cfg["rest_layers"]):
        x = _mlp(x * w["rest"]["ln"][i].astype(jnp.float32), w["rest"]["w1"][i], w["rest"]["w2"][i])
    logits = x @ w["embed"].astype(jnp.float32).T
    if quant == "int8":
        logits = jnp.round(logits * 4) / 4
    return jax.lax.dynamic_slice_in_dim(logits, start, n_pos, axis=0)
'''

TOY_READER = '''
"""Least time of the toy kernel's calls, in us, through the family's costs."""
from sbench.flops import kernel_needs


def read(ctx):
    needs = kernel_needs(ctx.family.KERNELS["toy_mlp"], ctx.cfg,
                         (rows for _, _, rows in ctx.decode_calls), ctx.peaks)
    return 1e6 * sum(n * t for n, t in needs) if needs else None
'''

TOY_CONFIG = {"name": "toy-tiny", "source": "test fixture: a family the harness never saw",
              "family": "toy", "hidden_size": 32, "vocab_size": 64, "first_width": 48,
              "rest_width": 80, "rest_layers": 3, "reduced": {}, "serve": {"n_pairs": 1}}


def _tree_digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_family_added_by_files_alone(tmp_path):
    bench = tmp_path / "benchmarks" / "streambench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH.parents[1] / "BENCHMARK.json", tmp_path)
    before = _tree_digest(tmp_path)

    (bench / "families" / "toy.py").write_text(TOY_FAMILY)
    (bench / "reference" / "toy.py").write_text(TOY_REFERENCE)
    (bench / "configs" / "toy-tiny.json").write_text(json.dumps(TOY_CONFIG))
    (bench / "cells" / "toy-tiny.chat.json").write_text(
        json.dumps({"rate_per_s": 1.0, "max_logit_gap": 0.01}))
    (bench / "metrics" / "toy_mlp_us.chat.py").write_text(TOY_READER)
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy-tiny", "source": "test fixture",
                          "file": "benchmarks/streambench/configs/toy-tiny.json",
                          "reduced": [], "why": "a new family"})
    bm["workloads"].append({"name": "toy-tiny.chat", "config": "toy-tiny", "traffic": "chat",
                            "chips": 1, "why": "a new family"})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("toy-tiny.chat")
    bm["per_layer"].append({"name": "toy_mlp_us.chat", "unit": "us", "better": "lower",
                            "source": "device_trace", "layer": "kernels", "moves": "tpot_p90_s",
                            "workloads": ["toy-tiny.chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    after = _tree_digest(tmp_path)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}, "an existing file changed"

    cell = spec.load_cell("toy-tiny.chat", root=tmp_path)
    fam = cell.family
    assert fam.__file__ == str((bench / "families" / "toy.py").resolve())
    cfg = cell.config
    w = fam.make_weights(cfg, 2**31 + 3)
    # layer groups unlike the qwen stack: a lone layer and a stack of another width
    assert set(w) == {"embed", "first", "rest"}
    assert w["first"]["w1"].shape == (32, 48) and w["rest"]["w1"].shape == (3, 32, 80)
    assert "ln" in w["rest"] and "ln" not in w["first"]
    again = fam.make_weights(cfg, 2**31 + 3)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(again)))
    # the reference the harness finds for this cell, and the check's gaps through it
    toks = np.arange(24, dtype=np.int32) % 64
    ref = np.asarray(cell.reference.logits_at(cfg, w, toks, 4, 8))
    assert ref.shape == (8, 64) and np.isfinite(ref).all()
    cell.traffic = dict(cell.traffic, prompt={"max": 4}, answer={"max": 8})
    seq = toks[:5].tolist()
    for _ in range(8):   # greedy answer by the reference itself
        seq.append(int(np.asarray(cell.reference.logits_at(
            cfg, w, np.pad(seq, (0, 128 - len(seq))), len(seq) - 1, 1))[0].argmax()))
    gaps, cgaps = harness.reference_gaps(cell, 2**31 + 3, [(seq[:5], seq[5:])],
                                         control="int8")
    assert harness.widest(gaps) == 0.0 and len(cgaps) == 1
    # the cell's reader goes through the family's own costs
    ctx = SimpleNamespace(cfg=cfg, family=fam, decode_calls=[(4, 1, [(10, 1), (3, 1)])],
                          peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert [m["name"] for m in cell.per_layer] == ["toy_mlp_us.chat"]
    got = spec.metric_reader("toy_mlp_us.chat", cell.metrics_dir)(ctx)
    want = 1e6 * 3 * max(4 * 32 * 80 * 2 / 1e12, 2 * 2 * 32 / 1e9)
    assert got == pytest.approx(want)
    assert fam.kv_bytes_per_token(cfg) == 2 * 32 * 4
    # the cells that were there still find their own family
    assert spec.load_cell("qwen3-1.7b.chat", root=tmp_path).family.Dims.of(
        spec.load_json(BENCH / "configs" / "qwen3-1.7b.json")).n_layers == 28


def test_missing_family_is_an_error_that_names_the_file(tmp_path):
    with pytest.raises(FileNotFoundError, match=r"families/nope\.py"):
        spec.family({"name": "x", "family": "nope"}, tmp_path)
    with pytest.raises(FileNotFoundError, match=r"reference/nope\.py"):
        spec.reference({"name": "x", "family": "nope"}, tmp_path)
    with pytest.raises(KeyError, match="names no"):
        spec.family({"name": "x"})


# Taken with the code before the qwen family became a file of its own
# (weights hashed leaf by leaf in path order, as uint16 bits).
ROWS = [(37, 1), (500, 9), (1023, 4), (1, 1)]
LENS = [402, 1020, 1536, 16]
PINNED = {
    "qwen2-tiny": dict(kv=256, step=15051520.0, prefill=2866273536.0,
                       dec=(5559680.0, 211328.0), flash=(1140649600.0, 2284032.0),
                       weights="2157cf7efd523fd8a7e33034a99222af691b89736aa35f879c291d00c5429a1d"),
    "qwen3-tiny": dict(kv=256, step=7642624.0, prefill=1351315968.0,
                       dec=(2223872.0, 205568.0), flash=(456259840.0, 1142016.0),
                       weights="1046ed1dbb7cb59745e66114bf9bd944ec5a20415fda6f277054dca2b7980154"),
    "qwen3-1.7b": dict(kv=114688, step=53606121472.0, prefill=8793732120576.0,
                       dec=(71163904.0, 6578176.0), flash=(14600314880.0, 36544512.0),
                       roofline=0.33027762460977234, mfu=0.5620260843130055),
    "qwen2.5-14b": dict(kv=49152, step=124582379520.0, prefill=20090567639040.0,
                        dec=(177909760.0, 6762496.0), flash=(36500787200.0, 73089024.0),
                        roofline=0.14221255844019182, mfu=1.283943605161274),
}
PATHS = {"qwen2-tiny": FIX / "qwen2-tiny.json", "qwen3-tiny": FIX / "qwen3-tiny.json",
         "qwen3-1.7b": BENCH / "configs" / "qwen3-1.7b.json",
         "qwen2.5-14b": BENCH / "configs" / "qwen2.5-14b.json"}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_qwen_numbers_did_not_move(name):
    cfg, want = spec.load_json(PATHS[name]), PINNED[name]
    fam = spec.family(cfg)
    m = fam.Dims.of(cfg)
    assert fam.kv_bytes_per_token(cfg) == want["kv"]
    assert fam.decode_step_flops(cfg, ROWS) == want["step"]
    assert fam.prefill_flops(cfg, LENS) == want["prefill"]
    assert fam.decode_attention_cost(m, ROWS) == want["dec"]
    assert fam.flash_attention_cost(m, LENS) == want["flash"]
    assert fam.KERNELS["decode_attention"](cfg, ROWS) == (m.n_layers, *want["dec"])
    if "weights" in want:
        h = hashlib.sha256()
        leaves = jax.tree_util.tree_leaves_with_path(fam.make_weights(cfg, 2**31 + 5))
        for path, x in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
            h.update(jax.tree_util.keystr(path).encode())
            h.update(np.asarray(x).view(np.uint16).tobytes())
        assert h.hexdigest() == want["weights"]
    if "roofline" in want:
        ctx = SimpleNamespace(
            cfg=cfg, family=fam, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            trace=SimpleNamespace(kernel_s=lambda k: 0.731, busy_s=7.99),
            decode_calls=[(16, 9, [(37, 1), (500, 9), (1023, 4)]), (16, 1, [(1200, 1)] * 13)],
            prefill_calls=[[402, 1020], [1536]])
        assert spec.metric_reader("decode_attention_roofline.chat")(ctx) == want["roofline"]
        assert spec.metric_reader("step_mfu.chat")(ctx) == want["mfu"]
