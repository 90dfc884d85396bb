"""§Perf hillclimb driver: lower + compile one cell under config overrides and
report the three roofline terms — the measurement half of the
hypothesis -> change -> measure -> validate loop.

    PYTHONPATH=src python -m repro.launch.perf_compare --arch gemma2-9b \
        --shape train_4k --set bf16_weight_gather=False --set moe_group=512
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import json
import time

import jax


def measure(arch: str, shape: str, overrides: dict, multi_pod: bool = False) -> dict:
    from repro import configs
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS
    from repro.launch.specs import build_step

    cfg = configs.get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_step(cfg, shape, mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            cell.step, in_shardings=cell.in_shardings, donate_argnums=cell.donate
        ).lower(*cell.args).compile()
    st = analyze_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "arch": arch,
        "shape": shape,
        "overrides": {k: str(v) for k, v in overrides.items()},
        "compile_s": round(time.time() - t0, 1),
        "t_compute_s": st.flops / PEAK_FLOPS,
        "t_memory_s": st.bytes_accessed / HBM_BW,
        "t_collective_s": st.collective_bytes / LINK_BW,
        "collectives": {k: v for k, v in st.collectives.items()},
        "mem_per_dev_gib": (
            mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
        ) / 2**30,
        "flops": st.flops,
        "bytes": st.bytes_accessed,
        "collective_bytes": st.collective_bytes,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", action="append", default=[], help="field=value overrides")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("True", "False"):
            v = v == "True"
        overrides[k] = v

    r = measure(args.arch, args.shape, overrides, args.multi_pod)
    if args.json:
        print(json.dumps(r, indent=1))
    else:
        print(
            f"{args.arch} {args.shape} {overrides or 'baseline-config'}\n"
            f"  compute   {r['t_compute_s']:10.4f} s  ({r['flops']:.3e} flops/dev)\n"
            f"  memory    {r['t_memory_s']:10.4f} s  ({r['bytes']:.3e} B/dev)\n"
            f"  collective{r['t_collective_s']:10.4f} s  ({r['collective_bytes']:.3e} B/dev)"
            f"  {({k: f'{v:.2e}' for k, v in r['collectives'].items()})}\n"
            f"  mem/dev   {r['mem_per_dev_gib']:10.2f} GiB   compile {r['compile_s']}s"
        )


if __name__ == "__main__":
    main()
