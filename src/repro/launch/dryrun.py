import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ruff: noqa: E402  — the lines above MUST precede any jax import: jax
# locks the device count at first initialization, and a CPU-only tool
# must never start the TPU backend (it would hold the chip).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh).

For each combination this proves the distribution config is coherent —
sharding mismatches, non-divisible dims or unsupported collectives fail
here — and extracts the roofline terms (launch.roofline) from the
compiled artifact.  Results stream to stdout and, with --out, to a JSON
lines file that benchmarks/roofline_table.py renders into
EXPERIMENTS.md §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.jsonl
"""
import argparse
import json
from repro.serving.telemetry import default_clock
import traceback

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, applicable, get_config
from repro.launch import presets as pz
from repro.launch import roofline as rl
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.training import optimizer as opt
from repro.training import trainer as tr


def run_one(arch: str, shape_name: str, mesh_name: str, *,
            preset: pz.RunPreset, verbose: bool = True) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = M.specialize(get_config(arch), shape)
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    cfg = cfg.replace(param_dtype=preset.param_dtype,
                      moe_rowwise=preset.moe_rowwise)
    tcfg = tr.TrainConfig(
        optimizer=opt.OptimizerConfig(moments_dtype=preset.moments_dtype),
        microbatches=preset.microbatches, remat=preset.remat,
        accum_dtype=preset.accum_dtype)

    t0 = default_clock()
    try:
        built = sp.build(cfg, shape, mesh, tcfg=tcfg, fsdp=preset.fsdp,
                         smart=preset.smart)
        lowered = built.fn.lower(*built.args)
        t_lower = default_clock() - t0
        compiled = lowered.compile()
        t_compile = default_clock() - t0 - t_lower
    except Exception as e:  # a failure HERE is a bug in the system
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}

    chips = mesh.devices.size
    r = rl.analyze(compiled, arch=arch, shape_name=shape_name,
                   mesh_name=mesh_name, chips=chips,
                   model_flops=rl.model_flops_for(cfg, shape))
    rec = {"status": "ok", **r.to_dict(),
           "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
           "preset": preset.__dict__,
           "memory_analysis": str(compiled.memory_analysis())}
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compute={r.compute_s:.3e}s memory={r.memory_s:.3e}s "
              f"collective={r.collective_s:.3e}s -> {r.bottleneck}-bound; "
              f"args/chip={r.arg_bytes_per_chip/2**30:.2f}GiB "
              f"temp/chip={r.temp_bytes_per_chip/2**30:.2f}GiB "
              f"useful={r.useful_ratio:.2f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="full grid: every (arch x shape)")
    ap.add_argument("--preset", choices=("baseline", "optimized"),
                    default="baseline")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        combos = [(a, s, m) for a in ARCH_IDS for s in INPUT_SHAPES
                  for m in meshes]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required without --all")
        combos = [(args.arch, args.shape, m) for m in meshes]

    getter = pz.baseline if args.preset == "baseline" else pz.optimized
    n_ok = n_skip = n_err = 0
    for arch, shape, mesh in combos:
        rec = run_one(arch, shape, mesh, preset=getter(arch))
        rec.setdefault("preset_name", args.preset)
        if rec["status"] == "ok":
            n_ok += 1
        elif rec["status"] == "skipped":
            n_skip += 1
            print(f"[{arch} x {shape} x {mesh}] SKIP: {rec['reason']}")
        else:
            n_err += 1
            print(f"[{arch} x {shape} x {mesh}] ERROR: {rec['error']}")
        if args.out:
            with open(args.out, "a") as f:
                rec.pop("trace", None)
                f.write(json.dumps(rec) + "\n")
    print(f"dry-run done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
