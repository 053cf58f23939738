import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first backend initialisation).

"""Multi-pod dry-run driver.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--force]
  python -m repro.launch.dryrun --list

``--all`` drives every (assigned arch × shape) cell through a subprocess per
cell (compile state isolation + restartability); results land in
experiments/dryrun/<mesh>_<arch>_<shape>.json and EXPERIMENTS.md §Dry-run is
generated from them.

This is a CPU tool: it compiles for 512 virtual host devices.  One child
process per cell is acceptable only because no child touches an
accelerator — on a chip, one process owns the device, and a child that
needs it fails or hangs while its parent holds it.
"""
import argparse
import json
import pathlib
import subprocess
import sys

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def cell_path(mesh: str, arch: str, shape: str) -> pathlib.Path:
    return RESULTS_DIR / f"{mesh}_{arch}_{shape}.json"


def run_one(arch: str, shape: str, mesh: str, spec_tokens: int = 0) -> int:
    from repro.launch.dryrun_lib import lower_cell

    res = lower_cell(arch, shape, mesh, spec_tokens=spec_tokens)
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"_spec{spec_tokens}" if spec_tokens else ""
    path = RESULTS_DIR / f"{mesh}_{arch}_{shape}{suffix}.json"
    path.write_text(json.dumps(res.to_json(), indent=2))
    print(
        f"[{res.status:7s}] {mesh:6s} {arch:24s} {shape:12s} "
        f"{res.seconds:7.1f}s flops/dev={res.flops_per_device:.3e} "
        f"bytes/dev={res.bytes_per_device:.3e} "
        f"coll={res.collectives.get('total', 0):.3e}B "
        f"{res.error[:60]}"
    )
    return 0 if res.status in ("ok", "skipped") else 1


def run_all(mesh_kinds, force: bool) -> int:
    from repro.configs import ASSIGNED
    from repro.configs.base import SHAPES

    failures = 0
    for mesh in mesh_kinds:
        for arch in ASSIGNED:
            for shape in SHAPES:
                path = cell_path(mesh, arch, shape)
                if path.exists() and not force:
                    prior = json.loads(path.read_text())
                    print(f"[cached ] {mesh:6s} {arch:24s} {shape:12s} ({prior['status']})")
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--mesh", mesh,
                ]
                rc = subprocess.call(cmd)
                if rc != 0:
                    failures += 1
                    print(f"[FAILED ] {mesh} {arch} {shape} rc={rc}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--spec-tokens", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        from repro.configs import ASSIGNED
        from repro.configs.base import SHAPES, shape_applicable
        from repro.configs import get_config

        for arch in ASSIGNED:
            for shape in SHAPES.values():
                ok, why = shape_applicable(get_config(arch), shape)
                print(f"{arch:24s} {shape.name:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        sys.exit(run_all(meshes, args.force))
    assert args.arch and args.shape, "--arch/--shape required (or --all)"
    rc = 0
    for m in meshes:
        rc |= run_one(args.arch, args.shape, m, args.spec_tokens)
    sys.exit(rc)


if __name__ == "__main__":
    main()
