"""The dry-run machinery itself: cell construction → lower → compile →
loop-aware profile, exercised on reduced configs over a multi-device
subprocess mesh (the same path the 512-device production dry-run takes)."""
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax
from repro.launch.mesh import make_mesh
from repro.configs import get_arch
from repro.configs.base import ShapeSpec
from repro.launch.cells import make_cell
from repro.launch.hlo_cost import analyze

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

CASES = [
    ("qwen3-1.7b", ShapeSpec("train_4k", "train", 32, 8)),
    ("mamba2-780m", ShapeSpec("decode_32k", "decode", 64, 8)),
    ("deepseek-moe-16b", ShapeSpec("prefill_32k", "prefill", 64, 4)),
]
for aid, sh in CASES:
    arch = get_arch(aid, reduced=True)
    cell = make_cell(arch, sh, mesh)
    compiled = cell.lower().compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    prof = analyze(compiled.as_text())
    assert prof["flops"] > 0, (aid, sh.name)
    assert prof["hbm_bytes"] > 0
    if sh.kind == "train":
        # the layer scan must be trip-count weighted (fwd + bwd loops)
        assert any(n >= 3 for _, n in prof["loops"]), prof["loops"]
    # stats must be JSON-serialisable (the sweep writes them per cell)
    json.dumps({"coll": prof["collective_bytes"],
                "counts": prof["collective_count"]})
    print("OK", aid, sh.name, int(prof["flops"]))
print("ALL OK")
"""


def test_cells_lower_compile_profile_subprocess():
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"},
                       cwd="/root/repo", timeout=900)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "ALL OK" in r.stdout
