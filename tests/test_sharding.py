"""Sharding helpers: axis filtering, divisibility degradation, spec
stacking, and cell construction on a multi-device mesh (subprocess)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import filter_spec, stack_spec, constrain


def test_filter_spec_no_mesh():
    # without a mesh every axis drops
    assert filter_spec(P("data", "model")) == P(None, None)


def test_constrain_identity_off_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, P("data", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_stack_spec():
    t = {"a": P("data", "model"), "b": {"c": P(None)}}
    s = stack_spec(t)
    assert s["a"] == P(None, "data", "model")
    assert s["b"]["c"] == P(None, None)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import set_mesh
from repro.launch.mesh import make_mesh
from repro.distributed.sharding import filter_spec, constrain
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
with set_mesh(mesh):
    # divisibility: dim 3 cannot shard 2-ways -> axis dropped
    assert filter_spec(P(("pod", "data"), "model"), (8, 3)) == \
        P(("pod", "data"), None), filter_spec(P(("pod","data"), "model"), (8, 3))
    # hybrid FSDP: bare 'data' expands over the pod axis on multi-pod meshes
    assert filter_spec(P("data", "model"), (8, 4)) == \
        P(("pod", "data"), "model"), filter_spec(P("data", "model"), (8, 4))
    # ...unless the dim doesn't divide the larger product (8 % 4 == 0, 2 % 4 != 0)
    assert filter_spec(P("data", None), (2, 4)) == P(None, None)
    # batch=1 decode cell: everything degrades to replication
    assert filter_spec(P(("pod", "data"),), (1,)) == P(None)
    # constrain under jit
    y = jax.jit(lambda x: constrain(x * 2, P(("pod", "data"), "model")))(
        jnp.ones((8, 4)))
    assert "model" in str(y.sharding.spec) or y.sharding.is_fully_replicated is False
print("OK")
"""


def test_filter_spec_divisibility_subprocess():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"},
                       cwd="/root/repo", timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


_SUBPROC_MOE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.launch.mesh import make_mesh
from repro.nn.ffn import MoEConfig, moe_init, moe_apply_dense, moe_apply_shard_map
mesh = make_mesh((2, 4), ("data", "model"))
cfg = MoEConfig(d_model=16, d_expert=8, num_experts=8, top_k=2,
                capacity_factor=8.0, sharding="ep")
p, _ = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
with set_mesh(mesh):
    y_ref, _ = moe_apply_dense(p, cfg, x)
    y_ep, _ = jax.jit(lambda pp, xx: moe_apply_shard_map(
        pp, cfg, xx, mesh, ep_axis="model", sp_axis=("data",)))(p, x)
err = float(jnp.abs(y_ref - y_ep).max())
assert err < 1e-4, err
print("OK", err)
"""


def test_moe_shard_map_matches_dense_subprocess():
    """EP all-to-all MoE == dense dispatch (8 experts over 4-way EP)."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_MOE],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"},
                       cwd="/root/repo", timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
