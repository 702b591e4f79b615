"""Each cell's chunk and weight packing, compiled for a TPU v5e that is
described, not attached (``v5e:2x2``), held to one chip's 16 GB.

Nothing runs: the chip's compiler refuses what does not fit or lower.  The
cell is built exactly as a run builds it (``cell.Cell``), on the described
devices.
"""
import json
import os

import jax
import numpy as np
import pytest

from conftest import BENCH, ROOT

HBM_BYTES = 16 * 1000 ** 3
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_resolve_interpret", lambda interpret: False)


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name", CELLS)
def test_cell_compiles_within_one_chip(topo, name):
    import cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = cell.load(BENCH, bench, name)
    devices = list(topo.devices)[:spec["chips"]]
    with jax.default_matmul_precision(spec["cfg"]["matmul_precision"]):
        c = cell.Cell(spec, devices)
        args = c.abstract_args()
        with jax.set_mesh(c.mesh):
            chunk = c.chunk.lower(*args).compile()
            ix = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,
                                                             a.dtype), c.ix)
            key = jax.ShapeDtypeStruct((2,), np.uint32)
            pack = jax.jit(c.layout.pack, out_shardings=c.param_sh).lower(
                key, ix).compile()
    assert _bytes(chunk) < HBM_BYTES, chunk.memory_analysis()
    assert _bytes(pack) < HBM_BYTES, pack.memory_analysis()
