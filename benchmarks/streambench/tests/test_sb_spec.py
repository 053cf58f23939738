"""BENCHMARK.json and the files it names: every file loads, the contract's
shape holds, and a new mix, cell or metric is taken up by name."""
import json
import re
import shutil

import jax
import jax.numpy as jnp
import pytest

from streambench_testlib import BENCH, ROOT, spec
from sbench import program

BM = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_contract_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert BM["paths"] == ["benchmarks/streambench"]
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BM[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    assert len({(w["config"], w["traffic"]) for w in BM["workloads"]}) == len(CELLS)
    for text in [w["why"] for w in BM["workloads"]] + [c["why"] for c in BM["configs"]] \
            + [m["layer"] for m in BM["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        for c in m["workloads"]:
            assert c in CELLS and c in e2e[m["moves"]].get("workloads", CELLS)
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads(cell):
    c = spec.load_cell(cell)
    assert {m["name"] for m in c.end_to_end} > {"setup_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    fam = c.family
    assert fam.kv_bytes_per_token(c.config) > 0
    assert callable(c.reference.logits_at)
    # the family's weights and the program's tree at full size, by shape only
    w = jax.eval_shape(lambda: fam.make_weights(c.config, 2**31 + 1))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(w))
    jax.eval_shape(lambda w: program.program_params(fam, c.config, fam.arch_config(c.config), w), w)
    assert c.config["source"].startswith("https://")
    if c.traffic["arrival"] == "poisson":
        assert c.data["rate_per_s"] > 0
    assert c.data["max_logit_gap"] > 0


def test_configs_state_their_cut():
    for c in BM["configs"]:
        cfg = spec.load_json(ROOT / c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key, cut in cfg["reduced"].items():
            assert cfg[key] == cut["here"] and cut["published"] != cut["here"]
        assert cfg["serve"]["n_pairs"] >= 1


def test_new_files_are_found_by_name(tmp_path):
    """A later PR adds a mix, a cell and a per-layer metric as new files and
    new entries only; the harness takes them up with no other edit."""
    bench = tmp_path / "benchmarks" / "streambench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "traffic" / "chat-bursty.json").write_text(json.dumps(
        dict(spec.load_json(BENCH / "traffic" / "chat.json"), burst=8)))
    (bench / "cells" / "qwen3-1.7b.chat-bursty.json").write_text(
        json.dumps({"rate_per_s": 3.0, "max_logit_gap": 1.0}))
    (bench / "metrics" / "burst_count.chat-bursty.py").write_text(
        "def read(ctx):\n    return 41.0 + 1\n")
    bm = json.loads(json.dumps(BM))
    bm["workloads"].append({"name": "qwen3-1.7b.chat-bursty", "config": "qwen3-1.7b",
                            "traffic": "chat-bursty", "chips": 1, "why": "bursts"})
    for m in bm["end_to_end"]:
        if m["name"] == "ttft_p90_s":
            m["workloads"].append("qwen3-1.7b.chat-bursty")
    bm["per_layer"].append({"name": "burst_count.chat-bursty", "unit": "1",
                            "better": "lower", "source": "host_clock", "layer": "load generator",
                            "moves": "ttft_p90_s", "workloads": ["qwen3-1.7b.chat-bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.load_cell("qwen3-1.7b.chat-bursty", root=tmp_path)
    assert cell.traffic["burst"] == 8 and cell.data["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["burst_count.chat-bursty"]
    assert spec.metric_reader("burst_count.chat-bursty", cell.metrics_dir)(None) == 42.0
    # the cells that were there are untouched by the addition
    assert spec.load_cell(CELLS[0], root=tmp_path).data == spec.load_cell(CELLS[0]).data
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=tmp_path)
