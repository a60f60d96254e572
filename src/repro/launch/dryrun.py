import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import — jax locks the device
count at first initialisation.  512 host devices back both the 16×16
single-pod mesh (256 chips) and the 2×16×16 multi-pod mesh (512 chips).

Per cell this driver:
  1. builds the step bundle (launch/steps.py) from ShapeDtypeStructs only,
  2. ``jax.jit(...).lower(...)`` with the cell's in/out shardings,
  3. ``.compile()`` — proving the sharding is coherent end-to-end,
  4. records ``memory_analysis()`` / ``cost_analysis()`` and the summed
     per-collective operand bytes parsed from the optimized HLO
     (launch/roofline.py) into results/dryrun/<arch>_<shape>_<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""
import argparse
import json
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun", **step_kw) -> dict:
    import jax

    from ..configs import get_config
    from . import roofline, steps
    from .mesh import make_production_mesh
    from .specs import SHAPES, shape_applicable

    cfg = get_config(arch)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}_{shape_name}_{mesh_name}"
    ok, reason = shape_applicable(cfg, shape_name)
    if not ok:
        rec = {"cell": cell, "status": "SKIP", "reason": reason}
        _write(out_dir, cell, rec)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        bundle = steps.build_step(cfg, mesh, shape_name, **step_kw)
        with mesh:
            jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                             out_shardings=bundle.out_shardings,
                             donate_argnums=bundle.donate)
            lowered = jitted.lower(*bundle.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        from . import hlo_analysis
        hlo = hlo_analysis.analyze(compiled.as_text())
        n_dev = int(mesh.devices.size)
        rec = {
            "cell": cell, "status": "OK", "arch": arch, "shape": shape_name,
            "mesh": mesh_name, "n_devices": n_dev,
            "kind": SHAPES[shape_name]["kind"],
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            # trip-count-aware walker (launch/hlo_analysis.py); XLA's own
            # cost_analysis counts while bodies once and is kept for x-check
            "flops_per_device": hlo["flops"],
            "bytes_per_device": hlo["bytes"],
            "xla_flops_per_device": cost.get("flops", 0.0),
            "xla_bytes_per_device": cost.get("bytes accessed", 0.0),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "generated_code_bytes": mem.generated_code_size_in_bytes,
            },
            "collectives": hlo["collectives"],
            "step_kw": {k: str(v) for k, v in step_kw.items()},
        }
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        rec = {"cell": cell, "status": "FAIL", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:],
               "elapsed_s": round(time.time() - t0, 1)}
    _write(out_dir, cell, rec)
    return rec


def _write(out_dir: str, cell: str, rec: dict):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell + ".json"), "w") as f:
        json.dump(rec, f, indent=2, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--serve-dtype", default="packed4",
                    choices=("packed4", "bf16"))
    args = ap.parse_args()

    from ..configs import list_configs
    from .specs import SHAPES

    archs = [args.arch] if args.arch else list_configs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    if args.all:
        archs = list_configs()
        shapes = list(SHAPES)
    pods = []
    if not args.multi_pod_only:
        pods.append(False)
    if not args.single_pod_only:
        pods.append(True) if (args.multi_pod or args.all
                              or args.multi_pod_only) else None

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                kw = ({"remat": args.remat}
                      if SHAPES[shape]["kind"] == "train"
                      else {"serve_dtype": args.serve_dtype})
                rec = run_cell(arch, shape, mp, out_dir=args.out, **kw)
                status = rec["status"]
                extra = (f" flops/dev={rec['flops_per_device']:.3g}"
                         if status == "OK" else
                         rec.get("reason", rec.get("error", ""))[:120])
                print(f"[{status:4s}] {rec['cell']}: {extra}", flush=True)
                failures += status == "FAIL"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
