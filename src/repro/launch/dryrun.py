import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(*abstract_inputs).compile()`` must succeed
on the single-pod (16x16) and the 2-pod (2x16x16) production meshes for all
40 (architecture x input-shape) cells; ``memory_analysis()`` proves the
per-chip footprint fits a 16 GB v5e and ``cost_analysis()`` + the HLO
collective inventory feed EXPERIMENTS.md §Roofline.

The device-count override above MUST precede any jax import (jax locks the
device count on first backend init) and is deliberately NOT set anywhere
else — tests and benchmarks see the single real CPU device.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all --mesh both --out benchmarks/artifacts
"""
import argparse
import json
import time
import traceback

import jax

from repro import configs as cfg_reg
from repro.launch import analysis, mesh as mesh_lib, specs

V5E_HBM_BYTES = 16 * 1024 ** 3


def run_cell(arch: str, shape_id: str, mesh_name: str,
             keep_hlo: bool = False) -> dict:
    """Lower+compile one cell; returns the JSON-able record."""
    ok, why = specs.applicable(arch, shape_id)
    rec = {"arch": arch, "shape": shape_id, "mesh": mesh_name}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_name == "pod2"))
    n_dev = mesh.size
    t0 = time.monotonic()
    try:
        from repro.dist import sharding as shd
        with shd.use_mesh(mesh):
            fn, args, donate, out_sh = specs.build_cell(arch, shape_id,
                                                        mesh)
            jitted = jax.jit(fn, donate_argnums=donate,
                             out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0 - t_lower

            cost = compiled.cost_analysis()
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
        colls = analysis.collective_stats(hlo, n_dev)
        cfg = specs.runtime_config(arch)
        shape = specs.SHAPES[shape_id]
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        # loop-aware reconstruction: XLA cost_analysis counts while bodies
        # once; these multiply by parsed trip counts (validated vs unrolled
        # lowerings — see tests/test_analysis.py)
        la_flops, la_bytes, la_wire = analysis.loop_aware_cost(hlo, n_dev)
        terms = analysis.roofline_terms(la_flops, la_bytes, la_wire)
        mf = analysis.model_flops(cfg, shape, n_dev)
        dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        rec.update(
            status="ok",
            n_devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            hlo_flops_per_dev=la_flops,
            hlo_bytes_per_dev=la_bytes,
            collective_wire_bytes_loop_aware=la_wire,
            xla_reported_flops=flops,
            xla_reported_bytes=bytes_acc,
            transcendentals=float(cost.get("transcendentals", 0.0)),
            collectives={k: v for k, v in colls.items()
                         if isinstance(v, dict) and v["count"]},
            collective_wire_bytes=colls["total_wire_bytes"],
            collective_count=colls["total_count"],
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "per_device_bytes": dev_bytes,
                "fits_v5e_16g": bool(dev_bytes < V5E_HBM_BYTES),
            },
            roofline=terms,
            model_flops_per_dev=mf,
            useful_flops_frac=(mf / la_flops if la_flops else 0.0),
            hlo_lines=hlo.count("\n"),
        )
        if keep_hlo:
            rec["hlo_text"] = hlo
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def cell_list(archs, shapes):
    return [(a, s) for a in archs for s in shapes]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None,
                    help="architecture id (see repro.configs.LM_IDS)")
    ap.add_argument("--shape", default=None, choices=specs.SHAPE_IDS)
    ap.add_argument("--mesh", default="pod1", choices=("pod1", "pod2",
                                                       "both"))
    ap.add_argument("--all", action="store_true",
                    help="sweep all (arch x shape) cells")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun",
                    help="artifact dir (one JSON per cell)")
    args = ap.parse_args()

    if args.all:
        cells = cell_list(cfg_reg.LM_IDS, specs.SHAPE_IDS)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]
    meshes = ("pod1", "pod2") if args.mesh == "both" else (args.mesh,)

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch, shape_id in cells:
        for mesh_name in meshes:
            rec = run_cell(arch, shape_id, mesh_name)
            path = os.path.join(
                args.out, f"{arch}__{shape_id}__{mesh_name}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (f" compile={rec['compile_s']}s "
                         f"mem/dev={rec['memory']['per_device_bytes']/2**30:.2f}GiB "
                         f"dom={rec['roofline']['dominant']}")
            elif status == "failed":
                n_fail += 1
                extra = " " + rec["error"][:160]
            print(f"[{status:7s}] {arch} x {shape_id} x {mesh_name}{extra}",
                  flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
