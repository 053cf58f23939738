"""Find a cell's files by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    configs/<config>.json      model sizes as published, the cut, the serve settings,
                               and the model family (``"family"``)
    families/<family>.py       a family's weights, program adapter and costs
    reference/<family>.py      the family's plain reference (``logits_at``)
    traffic/<traffic>.json     parameters of the one general traffic generator
    cells/<workload>.json      what belongs to one cell: its offered rate and its limits
    metrics/<metric>.py        one reader per per-layer metric (``read(ctx)``)

A later family, cell, mix or metric is added by adding files and entries; no
file that is already there needs an edit.

A family module supplies:

    make_weights(cfg, seed)         bf16 weights on the device, any pytree made
                                    from ``sbench.weights.weight_key(seed)``
    arch_config(cfg)                the program's ``ArchConfig`` (imports
                                    ``repro`` inside the function)
    to_program(cfg, arch, w)        the program's parameter tree holding ``w``
    kv_bytes_per_token(cfg)         cache bytes one token holds over all layers
    decode_step_flops(cfg, rows)    model FLOPs of one decode/verify call
    prefill_flops(cfg, prompt_lens) model FLOPs of one prefill call
    KERNELS                         kernel name -> ``cost(cfg, work)``: the
                                    kernel's ``(calls, flops, bytes)`` in one
                                    program execution, FLOPs and bytes of one call
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    data: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path = BENCH_DIR

    @property
    def metrics_dir(self) -> Path:
        return self.bench_dir / "metrics"

    @property
    def family(self) -> ModuleType:
        return family(self.config, self.bench_dir)

    @property
    def reference(self) -> ModuleType:
        return reference(self.config, self.bench_dir)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read from
    the benchmark's directory (the first of ``paths``)."""
    bench = benchmark(root)
    bench_dir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    data = load_json(bench_dir / "cells" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), cfg, traffic, data, e2e, per_layer, bench_dir)


def load_module(path: Path, kind: str) -> ModuleType:
    """The Python file ``path`` as a module, executed once per process (a
    family's jitted functions then keep their compiled programs)."""
    path = path.resolve()
    name = f"sb_{kind}_" + re.sub(r"\W", "_", str(path))
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _family_name(cfg: Dict[str, Any]) -> str:
    if "family" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no \"family\"")
    return cfg["family"]


def family(cfg: Dict[str, Any], bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The module ``families/<family>.py`` of the family that ``cfg`` names."""
    return load_module(bench_dir / "families" / f"{_family_name(cfg)}.py", "family")


def reference(cfg: Dict[str, Any], bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The plain reference ``reference/<family>.py`` of that family."""
    return load_module(bench_dir / "reference" / f"{_family_name(cfg)}.py", "reference")


def metric_reader(name: str, metrics_dir: Path = BENCH_DIR / "metrics") -> Callable:
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return load_module(metrics_dir / f"{name}.py", "metric").read
