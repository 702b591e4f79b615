import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and extract the roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single            # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun                      # the full sweep

Per cell this prints/records:
  * compiled.memory_analysis()  — per-device bytes (proves it fits 16 GB)
  * compiled.cost_analysis()    — HLO flops / bytes accessed
  * collective payload bytes parsed from the post-SPMD HLO
  * the three roofline terms against TPU v5e constants
Failures (sharding mismatch, OOM at compile, unsupported collective) are
bugs in the framework — the sweep exits nonzero if any cell fails."""
# (no __future__ import: the XLA_FLAGS lines above must stay first)
import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np
from jax import set_mesh

from repro.configs import ALL_ARCH_IDS, ALL_SHAPES, get_arch, shape
from repro.launch.cells import make_cell
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.hlo_stats import op_histogram
from repro.launch.mesh import make_production_mesh, mesh_num_devices

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link (≈ per-chip usable collective bw)


def roofline_terms(flops_per_dev, bytes_per_dev, coll_bytes_per_dev):
    return {
        "compute_s": flops_per_dev / PEAK_FLOPS,
        "memory_s": bytes_per_dev / HBM_BW,
        "collective_s": coll_bytes_per_dev / ICI_BW,
    }


def _decode_eff(cell, sh, chips, bytes_dev):
    if sh.kind != "decode" or not bytes_dev:
        return None
    ideal = (2.0 * cell.meta.get("active_params", 0)
             + cell.meta.get("kv_cache_bytes", 0)) / chips
    return ideal / bytes_dev


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             keep_hlo: bool = False) -> dict:
    arch = get_arch(arch_id)
    sh = shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_num_devices(mesh)
    t0 = time.time()
    with set_mesh(mesh):
        cell = make_cell(arch, sh, mesh)
        lowered = cell.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    # loop-aware static profile (XLA's cost_analysis counts while bodies
    # once — see hlo_cost.py); raw XLA numbers kept for reference
    prof = hlo_analyze(hlo)
    flops_dev = float(prof["flops"])
    bytes_dev = float(prof["hbm_bytes"])
    coll_dev = float(prof["total_collective_bytes"])
    terms = roofline_terms(flops_dev, bytes_dev, coll_dev)
    dominant = max(terms, key=terms.get)
    model_flops_dev = cell.model_flops / chips
    out = {
        "cell": cell.name,
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes),
        },
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "collective": {
            "per_device_bytes": prof["collective_bytes"],
            "counts": prof["collective_count"],
            "total_per_device_bytes": coll_dev,
        },
        "loops": prof["loops"],
        "xla_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": terms,
        "dominant": dominant,
        "model_flops_total": cell.model_flops,
        "model_flops_per_dev": model_flops_dev,
        "useful_flops_ratio": (model_flops_dev / flops_dev
                               if flops_dev else 0.0),
        "roofline_fraction": (model_flops_dev / PEAK_FLOPS
                              / max(sum(terms.values()), 1e-30)),
        "bound_fraction": (model_flops_dev / PEAK_FLOPS
                           / max(max(terms.values()), 1e-30)),
        "meta": cell.meta,
        # decode cells are HBM-bound by construction (one token against
        # params+cache); the honest efficiency metric is ideal-read-time /
        # modelled-memory-time, not a flops fraction
        "decode_mem_efficiency": _decode_eff(cell, sh, chips, bytes_dev),
        "op_histogram": op_histogram(hlo),
    }
    if keep_hlo:
        out["hlo_text"] = hlo
    return out


def cell_list(archs, shapes):
    for aid in archs:
        arch = get_arch(aid)
        for s in shapes:
            if arch.runs(s):
                yield aid, s
            else:
                yield aid, s  # skipped cells are still reported


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default=None, help="results dir (JSON per cell)")
    ap.add_argument("--hlo-dir", default=None, help="dump compiled HLO here")
    args = ap.parse_args(argv)

    archs = list(ALL_ARCH_IDS) if args.all else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for aid in archs:
        arch = get_arch(aid)
        for sname in shapes:
            if not arch.runs(sname):
                rec = {"cell": f"{aid}:{sname}", "arch": aid, "shape": sname,
                       "status": "skipped", "reason": arch.skip_reason}
                print(f"[skip] {aid}:{sname} — {arch.skip_reason}")
                if args.out:
                    _write(args.out, f"{aid}_{sname}_skip.json", rec)
                continue
            for mp in meshes:
                tag = "2x16x16" if mp else "16x16"
                label = f"{aid}:{sname}:{tag}"
                try:
                    rec = run_cell(aid, sname, mp, keep_hlo=bool(args.hlo_dir))
                    rec["status"] = "ok"
                    if args.hlo_dir:
                        hlo = rec.pop("hlo_text")
                        os.makedirs(args.hlo_dir, exist_ok=True)
                        with open(os.path.join(
                                args.hlo_dir,
                                f"{aid}_{sname}_{tag}.hlo"), "w") as f:
                            f.write(hlo)
                    peak_gb = rec["memory"]["peak_bytes"] / 2**30
                    print(f"[ok]   {label}  compile={rec['compile_s']:.0f}s "
                          f"peak={peak_gb:.2f}GiB "
                          f"dom={rec['dominant']} "
                          f"roofline={rec['roofline_fraction']:.3f}")
                    sys.stdout.flush()
                except Exception as e:  # noqa: BLE001
                    failures.append(label)
                    rec = {"cell": label, "arch": aid, "shape": sname,
                           "mesh": tag, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    print(f"[FAIL] {label}: {type(e).__name__}: {e}")
                    sys.stdout.flush()
                if args.out:
                    _write(args.out, f"{aid}_{sname}_{tag}.json", rec)
    if failures:
        print(f"\n{len(failures)} FAILED cells: {failures}")
        return 1
    return 0


def _write(outdir, name, rec):
    os.makedirs(outdir, exist_ok=True)
    rec = dict(rec)
    rec.pop("hlo_text", None)
    with open(os.path.join(outdir, name.replace(":", "_")), "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
