#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--out F]

For each seed, in one process: the program's first chunk against the
reference (the sound runs' readings); a second sound witness, the
reference summed in another order (``split``); the control, the
reference computed with every matmul operand rounded to 16 significant
bits, in the program's place; and the faults a training cell can have, planted in the reference
put in the program's place: half of each batch left out, and, on several
chips, the loss's exchange between chips left out (the total is then one
chip's part).  A state left unchanged reads 1 on ``change`` by its
measure and needs no run.  Prints one JSON line per seed; ``--out``
writes them to a file as well.  Takes no window, so no timing.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def as_program(ref: dict) -> dict:
    """A reference run in the shape of the program's readings."""
    return {"losses": ref["pers"].sum(axis=1), "pers": ref["pers"],
            "change": ref["change"], "moment": ref["moment"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    import numpy as np

    import cell as cellmod
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = cellmod.load(BENCH, bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print(f"needs {spec['chips']} TPU chips", file=sys.stderr)
        return 2
    devices = devices[:spec["chips"]]
    lines = []
    with jax.default_matmul_precision(spec["cfg"]["matmul_precision"]):
        c = cellmod.Cell(spec, devices)
        for seed in (int(s) for s in args.seeds.split(",")):
            with jax.set_mesh(c.mesh):
                c.setup(seed)
                c.free()
            ref = c.check()
            row = {"workload": args.workload, "seed": seed,
                   "program": c.numbers(ref),
                   "split": c.numbers(ref, as_program(
                       c.check(precision="split"))),
                   "control": c.numbers(ref, as_program(
                       c.check(precision="bf16x2"))),
                   "half_batch": c.numbers(ref, as_program(
                       c.check(batch_rows=c.batch // 2)))}
            w = row["program"]["_worst_member"]
            row["program_worst_member"] = list(c.members[w])
            # the members that open the widest gap in the worst leaf
            leaf = row["program"]["_change_leaf"].split("@")[0]
            d = np.abs(np.sqrt(c.prog["change"][leaf])
                       - np.sqrt(ref["change"][leaf]))
            row["change_worst_members"] = [
                [list(c.members[i]), float(d[i])]
                for i in np.argsort(d)[::-1][:3]]
            if c.chips > 1:
                prog = dict(c.prog)
                prog["losses"] = prog["pers"][:, c.chip_of == 0].sum(axis=1)
                row["exchange"] = c.numbers(ref, prog)
            lines.append(json.dumps(row))
            print(lines[-1], flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
