"""Every Pallas kernel carries a stable name into the program it is part of.

A ``pallas_call`` without ``name=`` reaches a TPU profile as the name of
the transformation around it (``jvp__.33``, ``transpose_jvp___.34``,
``shard_map.449``); with one, the compiled instruction and its location
carry the kernel's name (``jvp_fused_mid_fwd_.16``, ``fused_input_bwd.1``
under ``shard_map``), so a trace reduction can find each kernel after a
refactor.  The names are read from the source here, and the training
kernels are lowered for the TPU (lowering only: no compile, no chip) to
show that the name reaches the custom call's ``kernel_name``.
"""
import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deep
from repro.core.population import LayeredPopulation
from repro.kernels import ops

KERNELS = pathlib.Path(ops.__file__).resolve().parent
NAMES = {
    "fused_input.py": {"fused_input_fwd", "fused_input_infer",
                       "fused_input_infer_int8", "fused_input_bwd"},
    "fused_layer.py": {"fused_mid_fwd", "fused_mid_infer",
                       "fused_mid_infer_int8", "fused_mid_bwd"},
    "loss_head.py": {"loss_head_fwd", "loss_head_eval", "loss_head_bwd",
                     "loss_head_many_fwd", "loss_head_many_eval",
                     "loss_head_many_bwd"},
    "infer_head.py": {"infer_head", "infer_head_int8"},
    "moe_gemm.py": {"moe_gemm"},
    "flash_attn.py": {"flash_attn"},
}


def _pallas_calls(path: pathlib.Path) -> list:
    """``(line, [name literals])`` of every ``pallas_call(...)`` in a file;
    the list is None where the call passes no ``name=``."""
    out = []
    for node in ast.walk(ast.parse(path.read_text())):
        if (isinstance(node, ast.Call)
                and getattr(node.func, "attr", getattr(node.func, "id", None))
                == "pallas_call"):
            kw = next((k for k in node.keywords if k.arg == "name"), None)
            out.append((node.lineno, None if kw is None else [
                n.value for n in ast.walk(kw.value)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)]))
    return out


def test_every_kernel_file_is_listed():
    files = {p.name for p in KERNELS.glob("*.py") if _pallas_calls(p)}
    assert files == set(NAMES)


@pytest.mark.parametrize("fname", sorted(NAMES))
def test_every_pallas_call_is_named(fname):
    calls = _pallas_calls(KERNELS / fname)
    assert calls
    unnamed = [line for line, names in calls if not names]
    assert not unnamed, f"{fname}: pallas_call without name= at {unnamed}"
    assert {n for _, names in calls for n in names} == NAMES[fname]


def test_kernel_names_are_unique():
    seen = [n for p in sorted(KERNELS.glob("*.py"))
            for _, names in _pallas_calls(p) for n in names or ()]
    assert len(seen) == len(set(seen)) == sum(map(len, NAMES.values()))


# --------------------------------------------------------------------- #
# the training kernels lowered for the TPU                              #
# --------------------------------------------------------------------- #

B, F, BLOCK = 16, 8, 128


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    monkeypatch.setattr(ops, "_resolve_interpret", lambda interpret: False)


def _kernel_names(fn, *args) -> set:
    txt = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return set(re.findall(r'kernel_name = "([^"]*)"', txt))


def _input(x, w, b):
    return ops.fused_input(x, w, b, np.zeros(2, np.int32),
                           np.ones(2 * BLOCK, np.float32), block=BLOCK)


def _head(h, w, b):
    return ops.loss_head(h, w, b, jnp.zeros((B,), jnp.int32),
                         np.array([0, 1], np.int32), block_h=BLOCK)


def _mid_lp():
    return LayeredPopulation(F, 2, ((256, 128), (128, 128)),
                             (("relu", "relu"), ("tanh", "tanh")),
                             block=BLOCK).sorted()


def _mid(h, w, b):
    return deep.block_diag_fused(h, w, _mid_lp(), 0, bias=b)


def _mid_args():
    mid = deep.abstract_params(_mid_lp())["mid"][0]
    h_in = _mid_lp().layer_pop(0).total_hidden
    return (jnp.ones((B, h_in)), jax.tree.map(
        lambda a: jnp.ones(a.shape, a.dtype), mid["w"]),
        jnp.ones(mid["b"].shape, mid["b"].dtype))


CASES = {
    "fused_input": (_input, lambda: (jnp.ones((B, F)),
                                     jnp.ones((2 * BLOCK, F)),
                                     jnp.ones((2 * BLOCK,)))),
    "fused_mid": (_mid, _mid_args),
    "loss_head": (_head, lambda: (jnp.ones((B, 2 * BLOCK)),
                                  jnp.ones((2, 2 * BLOCK)),
                                  jnp.ones((2, 2)))),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_training_kernels_lower_with_their_names(kernel):
    """Forward and backward of a training step each carry their own name;
    the primal alone (an evaluation) lowers the no-gradient variant."""
    fn, args = CASES[kernel]

    def loss(*a):
        return jnp.sum(fn(*a))

    assert _kernel_names(jax.grad(loss, argnums=(0, 1)), *args()) == {
        f"{kernel}_fwd", f"{kernel}_bwd"}
    variant = {"loss_head": "loss_head_eval"}.get(kernel, f"{kernel}_infer")
    assert _kernel_names(loss, *args()) == {variant}
