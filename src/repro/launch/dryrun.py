import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# Set here (and ONLY here): smoke tests and benches see the real device.

"""Multi-pod dry-run: lower + compile EVERY (architecture x input-shape)
cell on the production meshes and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out results/dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k

Success of ``.lower().compile()`` for the 16x16 (single-pod, 256-chip) and
2x16x16 (multi-pod, 512-chip) meshes is the deliverable: sharding
mismatches, compile-time OOM, or unsupported collectives are bugs in the
framework.  Results append incrementally to the JSON so a crash resumes.
"""
import argparse
import json
import time
import traceback


from repro import configs
from repro.launch import cells as cells_mod
from repro.launch import hlo_collectives
from repro.launch.mesh import make_production_mesh

# TPU v5e-ish constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
LINK_BW = 2 * 50e9        # 2 usable ICI links per axis in a 2-axis torus


def run_cell(arch_id: str, cell_name: str, multi_pod: bool, keep_text: bool = False) -> dict:
    arch = configs.get(arch_id)
    cell = arch.cell(cell_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rec = {
        "arch": arch_id, "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "devices": n_dev,
    }
    t0 = time.time()
    built = cells_mod.build_cell(arch, cell, mesh, multi_pod)
    with jax.set_mesh(mesh):  # context for bare-PartitionSpec constraints
        lowered = built.lower()
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
    }
    # donated buffers (train state, KV caches) are input/output-aliased:
    # they exist once, so the aliased bytes are subtracted.
    rec["memory"]["total_per_device_bytes"] = (
        rec["memory"]["argument_bytes"] + rec["memory"]["output_bytes"]
        + rec["memory"]["temp_bytes"] - rec["memory"]["alias_bytes"]
    )
    ca = compiled.cost_analysis() or {}
    rec["cost"] = {
        "flops": float(ca.get("flops", -1)),
        "bytes_accessed": float(ca.get("bytes accessed", -1)),
    }
    text = compiled.as_text()
    rec["collectives"] = hlo_collectives.collective_bytes(text, n_dev)
    rec["collective_ops"] = hlo_collectives.collective_op_count(text)
    rec["static"] = {k: (float(v) if isinstance(v, (int, float)) else v)
                     for k, v in built.static.items()}
    # NOTE: scanned layer stacks are counted ONCE by HLO cost analysis; the
    # exact roofline terms come from launch/roofline.py (unrolled two-point
    # depth extrapolation).  Collective bytes above already multiply
    # while-loop trip counts.
    rec["hbm_ok"] = rec["memory"]["total_per_device_bytes"] < 16e9
    if keep_text:
        rec["hlo_text"] = text
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="cell name (default: all)")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--include-ann", action="store_true",
                    help="also run the paper-own ANN configs")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["cell"], r["mesh"]) for r in results if r.get("ok")}

    arch_ids = [args.arch] if args.arch else configs.all_ids(include_ann=args.include_ann)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch_id in arch_ids:
        arch = configs.get(arch_id)
        for cell in arch.cells:
            if args.shape and cell.name != args.shape:
                continue
            for multi_pod in meshes:
                mesh_name = "2x16x16" if multi_pod else "16x16"
                if (arch_id, cell.name, mesh_name) in done:
                    continue
                tag = f"{arch_id} x {cell.name} x {mesh_name}"
                try:
                    rec = run_cell(arch_id, cell.name, multi_pod)
                    rec["ok"] = True
                    gb = rec["memory"]["total_per_device_bytes"] / 1e9
                    print(
                        f"[ok]   {tag}: compile {rec['compile_s']}s, "
                        f"{gb:.2f} GB/dev, flops(1-iter) {rec['cost']['flops']:.3g}, "
                        f"coll {rec['collectives']['total'] / 1e6:.1f} MB/dev"
                    , flush=True)
                except Exception as e:
                    rec = {
                        "arch": arch_id, "cell": cell.name, "mesh": mesh_name,
                        "ok": False, "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                    n_fail += 1
                    print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:300]}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["cell"], r["mesh"]) != (arch_id, cell.name, mesh_name)]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"done: {len(results)} records, {n_fail} failures -> {args.out}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
