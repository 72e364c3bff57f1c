import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# the 512 devices are CPU host devices: pin the platform, so that on an
# accelerator host this script never claims the chip
os.environ["JAX_PLATFORMS"] = "cpu"

_DOC = """Exact roofline accounting (single-pod, per the assignment).

XLA's HLO cost analysis counts a while-loop body ONCE regardless of trip
count, so the plain dry-run undercounts scanned programs.  This sweep gets
exact numbers:

  * all internal lax.scan loops unroll (flags.unrolled_scans — flash chunks,
    CE chunks, microbatches, GRU, bulk-score map);
  * LM layer stacks compile UNROLLED at L∈{1,2} and extrapolate linearly:
        term(L) = term(1) + (L−1)·(term(2)−term(1))
    exact for layer-homogeneous transformers (embedding/unembed live in the
    L-independent base);
  * recsys/GNN cells have no layer loop — they compile directly, unrolled.

Artifacts: artifacts/roofline/<arch>__<shape>.json
"""

import argparse
import json
import time
import traceback

import jax

from repro import flags
from repro.configs import get
from repro.launch.dryrun import ART_DIR
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import SkippedCell, all_cells, build_cell
from repro.roofline import analysis as roofline


def _compile_cell(cell, mesh):
    donate = {"train": (0, 1), "decode": (2,)}.get(cell.kind, ())
    with mesh:
        with flags.unrolled_scans():
            compiled = jax.jit(
                cell.fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=donate).lower(*cell.args).compile()
    return compiled


def _terms(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = roofline.collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)),
            coll)


def account_cell(arch: str, shape: str, mesh) -> dict:
    fam = get(arch).family
    t0 = time.perf_counter()
    if fam == "lm":
        cell1 = build_cell(arch, shape, mesh, layers_override=1)
        cell2 = build_cell(arch, shape, mesh, layers_override=2)
        f1, b1, c1 = _terms(_compile_cell(cell1, mesh))
        f2, b2, c2 = _terms(_compile_cell(cell2, mesh))
        n_layers = get(arch).config.n_layers
        flops = f1 + (n_layers - 1) * (f2 - f1)
        byts = b1 + (n_layers - 1) * (b2 - b1)
        coll = {k: int(c1[k] + (n_layers - 1) * (c2[k] - c1[k])) for k in c1}
        # model_flops from the FULL config cell
        model_flops = build_cell(arch, shape, mesh).model_flops
        note = f"L-extrapolated from L=1,2 (full L={n_layers})"
    else:
        cell = build_cell(arch, shape, mesh)
        flops, byts, coll = _terms(_compile_cell(cell, mesh))
        model_flops = cell.model_flops
        note = "direct (unrolled scans)"

    rf = roofline.Roofline(flops=flops, bytes_accessed=byts, coll_bytes=coll,
                           chips=mesh.devices.size, model_flops=model_flops)
    return {"arch": arch, "shape": shape, "mesh": "single_pod_16x16",
            "accounting": "exact-unrolled", "note": note,
            "compile_s": round(time.perf_counter() - t0, 1),
            "roofline": rf.to_dict()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=False)
    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]

    out_dir = os.path.join(ART_DIR, "roofline")
    os.makedirs(out_dir, exist_ok=True)
    n_ok = n_fail = 0
    for arch, shape in cells:
        try:
            rec = account_cell(arch, shape, mesh)
            r = rec["roofline"]
            print(f"[roofline] {arch}×{shape}: compute {r['t_compute_s']:.2e}s "
                  f"memory {r['t_memory_s']:.2e}s coll {r['t_collective_s']:.2e}s "
                  f"→ {r['bottleneck']}; useful={r['useful_flops_ratio']:.2f} "
                  f"frac={r['roofline_fraction']:.3f} ({rec['compile_s']}s)",
                  flush=True)
            with open(os.path.join(out_dir, f"{arch}__{shape}.json"), "w") as f:
                json.dump(rec, f, indent=1)
            n_ok += 1
        except SkippedCell as e:
            print(f"[roofline] SKIP {e}", flush=True)
            with open(os.path.join(out_dir, f"{arch}__{shape}.json"), "w") as f:
                json.dump({"arch": arch, "shape": shape, "skipped": str(e)}, f)
        except Exception:
            print(f"[roofline] FAIL {arch}×{shape}", flush=True)
            traceback.print_exc()
            n_fail += 1
    print(f"[roofline] ok={n_ok} failed={n_fail}")


if __name__ == "__main__":
    main()
