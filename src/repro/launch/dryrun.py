import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first lines, before ANY other import: jax locks the device
# count on first init.  The dry-run (and ONLY the dry-run) sees 512
# placeholder devices so jax.make_mesh can build the production meshes.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell:
  with mesh: jax.jit(step, in_shardings, out_shardings).lower(...).compile()
then record memory_analysis() (proves it fits), cost_analysis() (FLOPs /
bytes for §Roofline) and the collective-bytes breakdown parsed from the
optimized HLO.  Results are written incrementally to results/dryrun/ as
JSON — re-runs skip completed cells (single-core container: the full sweep
takes a while).

Usage:
  python -m repro.launch.dryrun                    # all cells, both meshes
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list
"""
import argparse
import json
import time
import traceback

import jax

from repro.analysis import collective_bytes_from_hlo
from repro.analysis.hloflow import analyze_hlo
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")
RESULTS_DIR = os.path.abspath(os.path.join(
    os.environ.get("REPRO_RESULTS", os.getcwd()), "results", "dryrun"))


def cell_path(arch: str, shape: str, mesh_kind: str,
              variant: str = "baseline") -> str:
    suffix = "" if variant == "baseline" else f"__v-{variant}"
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def run_cell(arch: str, shape: str, mesh_kind: str, force: bool = False,
             variant: str = "baseline"):
    out_path = cell_path(arch, shape, mesh_kind, variant)
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            prior = json.load(f)
        if prior.get("ok"):
            print(f"[skip] {arch} x {shape} x {mesh_kind} x {variant} (done)")
            return prior
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "variant": variant,
           "mesh_shape": dict(zip(mesh.axis_names,
                                  [int(mesh.shape[a])
                                   for a in mesh.axis_names])),
           "ok": False}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            step, args, in_specs, out_specs, donate, meta = build_cell(
                arch, shape, mesh, variant=variant)
            rec.update(meta)
            jitted = jax.jit(step, in_shardings=in_specs,
                             out_shardings=out_specs,
                             donate_argnums=donate)
            t1 = time.time()
            lowered = jitted.lower(*args)
            t2 = time.time()
            compiled = lowered.compile()
            t3 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        cstats = collective_bytes_from_hlo(hlo)   # body-once (raw parse)
        flow = analyze_hlo(hlo)                   # trip-count-corrected
        rec.update({
            "ok": True,
            "lower_s": round(t2 - t1, 2),
            "compile_s": round(t3 - t2, 2),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_per_device_bytes": int(
                    ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
            },
            "cost": {k: float(v) for k, v in ca.items()
                     if isinstance(v, (int, float))},
            "collectives_raw": {
                "ops": dict(cstats.ops),
                "bytes_by_kind": {k: int(v) for k, v in
                                  cstats.bytes_by_kind.items()},
                "total_bytes": int(cstats.total_bytes),
            },
            # trip-count-corrected (see analysis/hloflow.py): the roofline
            # inputs. cost_analysis counts while bodies ONCE — verified.
            "flow": flow.as_dict(),
            "hlo_lines": hlo.count("\n"),
        })
        print(f"[ok]   {arch} x {shape} x {mesh_kind}: "
              f"peak={rec['memory']['peak_per_device_bytes']/1e9:.2f}GB/dev "
              f"dotflops={rec['flow']['dot_flops']:.3e}/dev "
              f"coll={rec['flow']['total_collective_bytes']/1e6:.1f}MB/dev "
              f"(compile {rec['compile_s']}s)")
    except Exception as e:  # noqa: BLE001 - record the failure, keep going
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} x {shape} x {mesh_kind}: {rec['error'][:200]}")
    rec["total_s"] = round(time.time() - t0, 2)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def iter_cells(archs=None, shapes=None, meshes=None):
    for arch in archs or ARCH_IDS:
        cfg = get_config(arch)
        for shape in shapes or SHAPES:
            if not shape_applicable(cfg, shape):
                continue
            for mesh_kind in meshes or ("single", "multipod"):
                yield arch, shape, mesh_kind


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append")
    ap.add_argument("--shape", action="append")
    ap.add_argument("--mesh", choices=["single", "multipod"])
    ap.add_argument("--variant", default="baseline",
                    help="comma-separated perf variants (see specs.py)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    meshes = [args.mesh] if args.mesh else None
    cells = list(iter_cells(args.arch, args.shape, meshes))
    if args.list:
        for c in cells:
            print(*c)
        return 0
    fails = 0
    for arch, shape, mesh_kind in cells:
        rec = run_cell(arch, shape, mesh_kind, force=args.force,
                       variant=args.variant)
        fails += 0 if rec.get("ok") else 1
    print(f"done: {len(cells) - fails}/{len(cells)} cells ok")
    return 1 if fails else 0


if __name__ == "__main__":
    raise SystemExit(main())
