"""CPU rehearsal of the chip benchmark: the benchmark's modules and the
system under test on the path, and a tiny cell in a temporary tree."""
import json
import os
import pathlib
import shutil
import sys

import pytest

# four virtual CPU devices for the member-sharded cell (set before JAX
# starts its backend)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

TINY = {
    "name": "tiny", "source": "test", "in_features": 6, "classes": 3,
    "block": 8, "samples": 64, "dtype": "float32",
    "matmul_precision": "highest",
    "population": {"kind": "grid", "hidden": [1, 5],
                   "activations": ["relu", "tanh", "gelu", "hardshrink"],
                   "repeats": 2},
    "optimizer": {"name": "sgd", "lr": 0.05},
}
TINY_TRAFFIC = {"name": "t16", "batch": 16, "scan_steps": 4}
# set from CPU readings of the tiny cells as the limits of the real cells
# are set from chip readings: sound runs read under 2e-7 on every number,
# the control above 1e-6 on ``member_loss_10th`` or ``change``
TINY_LIMITS = {"loss": 1e-5, "member_loss_10th": 1e-6, "change": 5e-7}


@pytest.fixture
def tiny_tree(tmp_path):
    """A copy of the benchmark's files plus one tiny configuration, traffic
    mix and cell, each added as a file of its own."""
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    meta = json.loads((ROOT / "BENCHMARK.json").read_text())

    def add(name, cfg, traffic, limits, chips=1):
        (bench / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
        (bench / "traffic" / f"{traffic['name']}.json").write_text(
            json.dumps(traffic))
        (bench / "workloads" / f"{name}.json").write_text(
            json.dumps({"limits": limits}))
        meta["workloads"].append({"name": name, "config": cfg["name"],
                                  "traffic": traffic["name"], "chips": chips,
                                  "why": "test"})
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(meta))

    return tmp_path, bench, add


def run_cell(root, name, seed=3, seconds=0.3, trace=0, wrap=None,
             chips=1, trace_dir=None):
    """One run of a cell of ``root``'s tree on the CPU."""
    import jax

    import run
    args = run.parse(["--workload", name, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)])
    return run.run(args, bench_dir=root / "chipbench", root=root,
                   devices=jax.devices()[:chips], wrap=wrap,
                   trace_dir=trace_dir)
