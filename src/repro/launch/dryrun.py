import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# the 512 devices are CPU host devices: pin the platform, so that on an
# accelerator host this script never claims the chip
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import/device init — jax locks device count on first use.

_DOC = """Multi-pod dry-run: lower + compile EVERY (architecture × input shape) cell
on the production meshes and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells, both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh single    # 16×16 only
    PYTHONPATH=src python -m repro.launch.dryrun --unroll         # roofline accounting
                                                                  #  (loops unrolled so
                                                                  #  cost_analysis is exact)

Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>.json
"""

import argparse
import json
import time
import traceback

import jax

from repro import flags
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import SkippedCell, all_cells, build_cell
from repro.roofline import analysis as roofline

ART_DIR = os.environ.get("REPRO_ARTIFACTS", "artifacts")


def run_cell(arch: str, shape: str, mesh, mesh_name: str, *, unroll: bool,
             fsdp: bool = False, verbose: bool = True) -> dict:
    t0 = time.perf_counter()
    cell = build_cell(arch, shape, mesh, fsdp=fsdp)
    # donation: train updates params+opt in place; decode updates caches —
    # without it the memory analysis double-counts the live state
    donate = {"train": (0, 1), "decode": (2,)}.get(cell.kind, ())
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=donate)
        if unroll:
            with flags.unrolled_scans():
                lowered = jitted.lower(*cell.args)
        else:
            lowered = jitted.lower(*cell.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    rf = roofline.from_compiled(compiled, chips=mesh.devices.size,
                                model_flops=cell.model_flops)
    rec = {
        "arch": arch, "shape": shape, "kind": cell.kind, "mesh": mesh_name,
        "chips": int(mesh.devices.size),
        "unrolled_accounting": unroll,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "roofline": rf.to_dict(),
        "note": cell.note,
    }
    if verbose:
        m = rec["memory"]["peak_bytes_per_device"] / 2**30
        r = rec["roofline"]
        print(f"[dryrun:{mesh_name}] {arch}×{shape}: compile {t_compile:.1f}s "
              f"peak/dev {m:.2f} GiB | compute {r['t_compute_s']:.2e}s "
              f"memory {r['t_memory_s']:.2e}s coll {r['t_collective_s']:.2e}s "
              f"→ {r['bottleneck']}-bound, useful={r['useful_flops_ratio']:.2f}")
    return rec


def save_record(rec: dict, mesh_name: str) -> str:
    d = os.path.join(ART_DIR, "dryrun", mesh_name)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{rec['arch']}__{rec['shape']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll internal scans for exact cost accounting")
    ap.add_argument("--fsdp", action="store_true")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    n_ok = n_skip = n_fail = 0
    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            try:
                rec = run_cell(arch, shape, mesh, mesh_name, unroll=args.unroll,
                               fsdp=args.fsdp)
                save_record(rec, mesh_name)
                n_ok += 1
            except SkippedCell as e:
                print(f"[dryrun:{mesh_name}] SKIP {e}")
                save_record({"arch": arch, "shape": shape, "mesh": mesh_name,
                             "skipped": str(e)}, mesh_name)
                n_skip += 1
            except Exception:
                print(f"[dryrun:{mesh_name}] FAIL {arch}×{shape}")
                traceback.print_exc()
                n_fail += 1
    print(f"\n[dryrun] ok={n_ok} skipped={n_skip} failed={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
