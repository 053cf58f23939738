"""Roofline analysis over the dry-run artifacts (deliverable g).

For every (arch × shape) single-pod cell, derive the three roofline terms
from the compiled dry-run statistics:

  compute_s    = HLO_FLOPs/device   / peak_FLOP/s         (197e12 bf16, v5e)
  memory_s     = HLO_bytes/device   / HBM_bw              (819e9 B/s)
  collective_s = collective_bytes/device / ICI link bw    (50e9 B/s)

plus MODEL_FLOPS (6·N·D train / 2·N·D serve; N = active params for MoE),
the useful-compute ratio MODEL_FLOPS / HLO_FLOPs, the dominant term, and a
bottleneck note.  Writes experiments/roofline.{json,md}.

HLO numbers come from the trip-count-corrected analyzer
(launch/hlo_analysis.py): XLA's cost_analysis counts while bodies once,
which would undercount scanned-layer stacks ~n_layers-fold.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional

from repro.configs import ASSIGNED, get_config
from repro.configs.base import SHAPES
from repro.serving.cost_model import TPU_V5E

ROOT = pathlib.Path(__file__).resolve().parent.parent
DRYRUN = ROOT / "experiments" / "dryrun"
OUT = ROOT / "experiments"
CHIPS_SINGLE = 256


def model_flops_per_device(arch: str, shape_name: str, chips: int = CHIPS_SINGLE) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n * tokens
    else:  # decode: one token per sequence per step
        total = 2.0 * n * shape.global_batch
    return total / chips


def analyze_cell(arch: str, shape_name: str, mesh: str = "single",
                 suffix: str = "") -> Optional[Dict]:
    path = DRYRUN / f"{mesh}_{arch}_{shape_name}{suffix}.json"
    if not path.exists():
        return None
    d = json.loads(path.read_text())
    if d["status"] != "ok":
        return {"arch": arch, "shape": shape_name, "status": d["status"],
                "note": d.get("error", "")}
    compute_s = d["flops_per_device"] / TPU_V5E.peak_flops
    memory_s = d["bytes_per_device"] / TPU_V5E.hbm_bw
    collective_s = d["collectives"].get("total", 0) / TPU_V5E.interconnect_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(arch, shape_name)
    useful = mf / d["flops_per_device"] if d["flops_per_device"] else 0.0
    # roofline fraction: useful work per step over the time the dominant
    # term pins the step to (= achievable fraction of the compute roofline)
    step_s = max(terms.values())
    roofline_frac = (mf / TPU_V5E.peak_flops) / step_s if step_s > 0 else 0.0
    return {
        "arch": arch,
        "shape": shape_name,
        "status": "ok",
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops_per_dev": mf,
        "hlo_flops_per_dev": d["flops_per_device"],
        "useful_ratio": useful,
        "roofline_frac": roofline_frac,
        "peak_mem_bytes": d["peak_memory_per_device"],
        "note": _bottleneck_note(dominant, useful, shape_name),
    }


def _bottleneck_note(dominant: str, useful: float, shape: str) -> str:
    if dominant == "compute":
        if useful < 0.5:
            return ("compute-bound with low useful ratio: cut remat recompute "
                    "/ padded-head waste before touching sharding")
        return "compute-bound near useful peak: only better MXU utilisation helps"
    if dominant == "memory":
        if shape.startswith("decode") or shape.startswith("long"):
            return ("memory-bound on weight+KV streaming: batch more sequences "
                    "per step, shard KV wider, or quantise KV")
        return "memory-bound: increase fusion / avoid re-materialised activations"
    return ("collective-bound: re-shard to cut all-gathers (keep weights "
            "model-sharded through the step), overlap collectives with compute")


def full_table() -> List[Dict]:
    rows = []
    for arch in ASSIGNED:
        for shape in SHAPES:
            r = analyze_cell(arch, shape)
            if r is not None:
                rows.append(r)
    return rows


def render_md(rows: List[Dict]) -> str:
    lines = [
        "| arch | shape | compute (s) | memory (s) | collective (s) | dominant | "
        "6ND/dev | useful | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] != "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | skipped | — | — | — |"
            )
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} | "
            f"{r['model_flops_per_dev']:.2e} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.3f} |"
        )
    return "\n".join(lines)


def main() -> None:
    rows = full_table()
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "roofline.json").write_text(json.dumps(rows, indent=2))
    md = render_md(rows)
    (OUT / "roofline.md").write_text(md)
    print(md)
    ok = [r for r in rows if r["status"] == "ok"]
    print(f"\n{len(ok)} cells analysed")
    worst = sorted(ok, key=lambda r: r["roofline_frac"])[:5]
    print("\nworst roofline fraction:")
    for r in worst:
        print(f"  {r['arch']:24s} {r['shape']:12s} frac={r['roofline_frac']:.4f} "
              f"dominant={r['dominant']}")
    coll = sorted(ok, key=lambda r: -(r["collective_s"] / max(r["compute_s"], r["memory_s"], 1e-12)))[:5]
    print("\nmost collective-bound (coll / max(other terms)):")
    for r in coll:
        ratio = r["collective_s"] / max(r["compute_s"], r["memory_s"], 1e-12)
        print(f"  {r['arch']:24s} {r['shape']:12s} ratio={ratio:.2f}")


if __name__ == "__main__":
    main()
