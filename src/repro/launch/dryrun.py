"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory/cost/collective accounting — proof that the distribution config
is coherent without real hardware.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Outputs one JSON per cell under results/dryrun/.
"""

# The host has ONE real CPU device; the dry-run needs 512 placeholder devices
# so jax.make_mesh can build the production meshes.  These two lines MUST run
# before any other import (jax locks the device count on first init).
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_step
from repro.models import count_active_params, count_params

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str, verbose: bool = True) -> dict:
    cfg = configs.get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    cell = build_step(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            cell.step, in_shardings=cell.in_shardings, donate_argnums=cell.donate
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax returns [dict] per device
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    stats = analyze_hlo(hlo)

    n_chips = mesh.devices.size
    record = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "n_chips": int(n_chips),
        "params": count_params(cfg),
        "active_params": count_active_params(cfg),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {
            k: v for k, v in cost.items() if k in ("flops", "bytes accessed")
        },
        "hlo_stats": stats.asdict(),
        "hlo_bytes": len(hlo),
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if verbose:
        print(
            f"[dryrun] OK {arch:24s} {shape:12s} {mesh_name:10s} "
            f"compile={t_compile:6.1f}s mem/dev={record['memory']['per_device_total']/2**30:7.2f}GiB "
            f"flops={stats.flops:.3e} coll={stats.collective_bytes:.3e}B",
            flush=True,
        )
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        archs = list(configs.ARCH_IDS)
    elif args.arch:
        archs = [args.arch]
    else:
        ap.error("--arch or --all required")

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        shapes = [args.shape] if args.shape else configs.cells(arch)
        for shape in shapes:
            for multi in meshes:
                mesh_name = "pod2x16x16" if multi else "pod16x16"
                path = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] skip {arch} {shape} {mesh_name} (exists)", flush=True)
                    continue
                try:
                    run_cell(arch, shape, multi, args.out)
                except Exception as e:
                    failures.append((arch, shape, mesh_name, repr(e)))
                    print(f"[dryrun] FAIL {arch} {shape} {mesh_name}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", *f)
        return 1
    print("\nall dry-run cells passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
